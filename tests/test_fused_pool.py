"""The fused SA-CONV -> maxpool flush epilogue (paper Fig. 7: the
pooling-&-activation unit sits after accumulation, before DRAM).

Exact-match parity grid of the fused conv+pool dispatch against the
unfused conv -> HBM -> standalone-pool composition, the planner's decline
paths (non-tiling pool, non-monotone act, VMEM budget overflow), the
plan-level fused-traffic accounting, standalone pools routed through the
engine, and the maxpool_act integer channel-padding regression.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.dataflow import MONOTONE_ACTS, PlanError, PoolSpec, plan_conv
from repro.core.engine import DispatchPolicy, Engine
from repro.core.perf_model import pallas_conv_traffic
from repro.core.schedule import LayerSchedule, clear_schedule_cache
from repro.kernels import ref
from repro.kernels.pool_act import maxpool_act


def _rand(key, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32) * scale


def _in_res(conv_stride: int, pool_window: int, pool_stride: int,
            kernel: int = 3) -> int:
    """Smallest input edge >= 10 whose conv OFM the pool windows tile."""
    for h in range(10, 40):
        oh = (h - kernel) // conv_stride + 1
        if (h - kernel) % conv_stride:
            continue
        if oh >= pool_window and (oh - pool_window) % pool_stride == 0:
            return h
    raise AssertionError("no resolution found")


# ---------------------------------------------------------------------------
# exact-match parity grid: fused epilogue == unfused composition, bitwise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("window", [2, 3])
@pytest.mark.parametrize("conv_stride", [1, 2])
@pytest.mark.parametrize("act", ["relu", "none"])
@pytest.mark.parametrize("wdtype", ["fp32", "int8"])
def test_fused_equals_unfused_exact(window, conv_stride, act, wdtype):
    pool_stride = 2
    res = _in_res(conv_stride, window, pool_stride)
    x = _rand(0, (2, res, res, 6))
    f = _rand(1, (3, 3, 6, 24), 0.2)
    w = quant.quantize(f) if wdtype == "int8" else f
    b = _rand(2, (24,))
    eng = Engine(backend="pallas")
    with eng.tracing() as tr:
        fused = eng.conv2d(x, w, b, stride=conv_stride, act=act,
                           pool=PoolSpec(window, pool_stride), name="c")
    assert tr[0].conv_plan.fuse_pool, tr.summary()
    assert len(tr) == 1                       # ONE dispatch, no pool pass
    conv = eng.conv2d(x, w, b, stride=conv_stride, act=act, name="c")
    unfused = maxpool_act(conv, window=window, stride=pool_stride,
                          act="none")
    assert fused.shape == unfused.shape
    # bitwise: max commutes exactly with monotone act / bias add / scale
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


def test_fused_matches_xla_oracle():
    """Pallas fused epilogue against the independent XLA conv+act+pool."""
    x = _rand(0, (2, 15, 15, 8))
    f = _rand(1, (3, 3, 8, 32), 0.2)
    b = _rand(2, (32,))
    pal = Engine(backend="pallas")
    xla = Engine(backend="xla")
    got = pal.conv2d(x, f, b, act="relu", pool=PoolSpec(3, 2))
    want = xla.conv2d(x, f, b, act="relu", pool=PoolSpec(3, 2))
    ref_out = ref.maxpool2d(
        ref.apply_act(ref.conv2d(x, f) + b, "relu"), window=3, stride=2)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(want, ref_out, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decline paths: the planner owns the decision, the engine falls back
# ---------------------------------------------------------------------------
def test_plan_declines_non_tiling_pool_and_engine_falls_back():
    """Odd OFM the 3s2 windows don't tile: fusion declined cleanly, the
    engine runs conv + standalone pool, numerics unchanged."""
    x = _rand(0, (2, 14, 14, 6))              # oh = 12, (12-3) % 2 == 1
    f = _rand(1, (3, 3, 6, 16), 0.2)
    b = _rand(2, (16,))
    eng = Engine(backend="pallas")
    with eng.tracing() as tr:
        got = eng.conv2d(x, f, b, act="relu", pool=PoolSpec(3, 2), name="c")
    assert not tr[0].conv_plan.fuse_pool
    assert len(tr) == 2 and tr[1].regime == "pool" and tr[1].name == "c.pool"
    want = ref.maxpool2d(ref.apply_act(ref.conv2d(x, f) + b, "relu"),
                         window=3, stride=2)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_plan_declines_non_monotone_act():
    """silu is not monotone: act(maxpool(.)) != maxpool(act(.)), so the
    planner must decline and the fallback must keep act-then-pool order."""
    assert "silu" not in MONOTONE_ACTS
    plan = plan_conv(2, 15, 15, 8, 3, 3, 32, bytes_in=4, bytes_w=4,
                     pool=PoolSpec(3, 2), act="silu")
    assert not plan.fuse_pool
    x, f = _rand(0, (2, 15, 15, 8)), _rand(1, (3, 3, 8, 32), 0.2)
    eng = Engine(backend="pallas")
    got = eng.conv2d(x, f, act="silu", pool=PoolSpec(3, 2))
    want = ref.maxpool2d(ref.apply_act(ref.conv2d(x, f), "silu"),
                         window=3, stride=2)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_plan_declines_on_vmem_budget_overflow():
    """A budget below even the minimum slab working set: the planner
    declines the op at schedule time with a PlanError naming the layer —
    never an over-budget plan for the chip's compiler to refuse.  At a
    budget that fits, the same op runs fused and matches the oracle."""
    with pytest.raises(PlanError) as ei:
        plan_conv(1, 21, 21, 64, 3, 3, 128, bytes_in=4, bytes_w=4,
                  vmem_budget=64 * 1024, pool=PoolSpec(3, 2), act="relu")
    assert ei.value.vmem_budget == 64 * 1024
    x, f = _rand(0, (1, 21, 21, 64)), _rand(1, (3, 3, 64, 128), 0.1)
    eng = Engine(backend="pallas",
                 policy=DispatchPolicy(vmem_budget=64 * 1024))
    with pytest.raises(PlanError) as ei:
        eng.conv2d(x, f, act="relu", pool=PoolSpec(3, 2), name="c")
    assert ei.value.op == "c"
    eng = Engine(backend="pallas",
                 policy=DispatchPolicy(vmem_budget=4 * 1024 * 1024))
    with eng.tracing() as tr:
        got = eng.conv2d(x, f, act="relu", pool=PoolSpec(3, 2), name="c")
    assert tr[0].conv_plan.fuse_pool
    want = ref.maxpool2d(ref.apply_act(ref.conv2d(x, f), "relu"),
                         window=3, stride=2)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# plan-level fused-traffic accounting
# ---------------------------------------------------------------------------
def test_fused_plan_credits_eliminated_ofm_roundtrip():
    """The fused plan's bytes drop by AT LEAST the eliminated OFM write +
    re-read vs. the unfused conv + standalone-pool composition."""
    bytes_out = 4
    rows = pallas_conv_traffic("alexnet", batch=1)
    fused_rows = [r for r in rows if r.plan.fuse_pool]
    assert len(fused_rows) == 3               # AlexNet's three conv+pool pairs
    from repro.models.cnn import network_stats
    ofm = {l.name: l.ofm for l in network_stats("alexnet")}
    for r in fused_rows:
        oh, ow, co = ofm[r.layer]
        assert r.fused_saving_bytes >= 2 * oh * ow * co * bytes_out, r
        assert r.plan.hbm_bytes >= r.compulsory_bytes
        # and the unfused ablation really is pool-free
    for r in pallas_conv_traffic("alexnet", batch=1, fuse_pool=False):
        assert not r.plan.fuse_pool and r.fused_saving_bytes == 0


def test_pooled_output_block_keeps_tap_fusion_alive():
    """The benchmark's headline mechanism (BENCH_conv_fused.json): under
    an accelerator-class VMEM budget, the pooled output block credited by
    ``fuse_pool`` is what keeps AlexNet conv1's 11x11 patch tile inside
    the budget — the fused plan contracts all 121 taps in one MXU pass
    per output row while the unfused plan must stream them.  Pin the flip
    so planner drift that moves the window shows up here, not as a silent
    perf regression.  (Both widths pad their channels to one 128-lane
    tile, so one budget serves both.)"""
    for co, budget in ((24, 9043968), (96, 9043968)):   # w=0.25 / w=1.0
        fused = plan_conv(1, 227, 227, 3, 11, 11, co, stride=4,
                          bytes_in=4, bytes_w=4, vmem_budget=budget,
                          pool=PoolSpec(3, 2), act="relu")
        unfused = plan_conv(1, 227, 227, 3, 11, 11, co, stride=4,
                            bytes_in=4, bytes_w=4, vmem_budget=budget)
        assert fused.fuse_pool and fused.fuse_taps, fused
        assert not unfused.fuse_taps, unfused
        # both plans honor the budget; the fused one only fits the patch
        # tile because the output block it charges is the pooled one
        assert fused.vmem_bytes <= budget and unfused.vmem_bytes <= budget


def test_schedule_and_roofline_carry_fused_traffic():
    from repro.core.roofline import (fused_pool_traffic_from_schedule,
                                     terms_from_schedule)
    clear_schedule_cache()
    sched = LayerSchedule.compile_cnn("alexnet", batch=1, in_res=67,
                                      width_mult=0.125)
    fused_keys = [k for k, p in sched.conv_entries.items() if p.fuse_pool]
    assert len(fused_keys) == 3
    assert all(k.pool_window == 3 and k.pool_stride == 2
               for k in fused_keys)
    rep = fused_pool_traffic_from_schedule(sched)
    assert sum(v["saving_bytes"] > 0 for v in rep.values()) == 3
    # the roofline HBM term is the fused commitment
    t = terms_from_schedule(sched)
    assert t.hbm_bytes_per_chip == sum(p.hbm_bytes for p in sched.plans())


# ---------------------------------------------------------------------------
# standalone pools go through the engine (trace visibility)
# ---------------------------------------------------------------------------
def test_standalone_pool_dispatches_through_engine():
    x = _rand(0, (2, 8, 8, 20))
    for backend in ("pallas", "xla"):
        eng = Engine(backend=backend)
        with eng.tracing() as tr:
            got = eng.pool(x, window=2, stride=2, name="pool1")
        assert len(tr) == 1 and tr[0].regime == "pool"
        assert tr[0].name == "pool1" and tr[0].backend == backend
        np.testing.assert_allclose(
            got, ref.maxpool2d(x, window=2, stride=2), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# maxpool_act integer channel-padding regression
# ---------------------------------------------------------------------------
def test_maxpool_act_int8_negative_channel_padding():
    """Channel padding must use the dtype's max-identity: int8 lanes padded
    with 0 (the old behaviour) instead of iinfo.min would poison any
    future cross-lane reduction; all-negative int8 maps must pool exactly
    like the reduce_window oracle, padded tile or not."""
    x = jax.random.randint(jax.random.PRNGKey(0), (2, 6, 6, 130),
                           -120, -1, jnp.int8)       # c=130 pads to 2*128
    got = maxpool_act(x, window=2, stride=2, act="none", bc=128)
    want = ref.maxpool2d(x, window=2, stride=2)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # unpadded path too
    got2 = maxpool_act(x[..., :64], window=2, stride=2, act="none")
    np.testing.assert_array_equal(
        np.asarray(got2), np.asarray(ref.maxpool2d(x[..., :64],
                                                   window=2, stride=2)))
