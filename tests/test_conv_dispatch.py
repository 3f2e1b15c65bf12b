"""The implicit-GEMM SA-CONV dispatch path: kernel equivalence over the
stride/pad/int8 grid, conv planning under engine policy, compiled-schedule
resolution, and the plan-vs-execution agreement the old path drifted on."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.dataflow import (MAX_TILE, classify_regime,
                                 compulsory_conv_bytes, plan_conv,
                                 plan_matmul)
from repro.core.engine import DispatchPolicy, Engine
from repro.core.perf_model import pallas_conv_traffic
from repro.core.schedule import LayerSchedule, clear_schedule_cache
from repro.kernels import ref
from repro.kernels.sa_conv import sa_conv_matmul
from repro.kernels.sa_conv_implicit import sa_conv_implicit
from repro.models import cnn


def _rand(key, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32) * scale


# ---------------------------------------------------------------------------
# kernel equivalence: the acceptance grid (stride x pad x int8)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("pad", [0, 1, 2])
def test_engine_conv2d_matches_ref_stride_pad(stride, pad):
    x = _rand(0, (2, 13, 15, 5))
    f = _rand(1, (3, 3, 5, 24), 0.2)
    b = _rand(2, (24,))
    eng = Engine(backend="pallas")
    got = eng.conv2d(x, f, b, stride=stride, pad=pad, act="relu")
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    want = ref.apply_act(ref.conv2d(xp, f, stride=stride) + b, "relu")
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("stride,pad", [(1, 1), (2, 2), (4, 0)])
def test_engine_conv2d_int8_weights(stride, pad):
    """int8 filters reach the kernel un-dequantized; the per-output-channel
    scale fuses into the accumulator-flush epilogue on both backends."""
    x = _rand(0, (2, 12, 12, 6))
    qt = quant.quantize(_rand(1, (3, 3, 6, 16), 0.2))
    b = _rand(2, (16,))
    pal = Engine(backend="pallas")
    xla = Engine(backend="xla")
    got = pal.conv2d(x, qt, b, stride=stride, pad=pad, act="relu")
    want = xla.conv2d(x, qt, b, stride=stride, pad=pad, act="relu")
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-3)
    with pal.tracing() as tr:
        pal.conv2d(x, qt, b, stride=stride, pad=pad, act="relu")
    assert tr[0].weight_dtype == "int8"
    # planned at 1 byte/weight
    assert tr[0].conv_plan is not None


def test_kernel_multi_ci_tile_and_streamed_taps():
    """The hard kernel branches: gi > 1 (cross-tile psum accumulation,
    init/flush on different grid steps) and fuse_taps=False (tap-wise
    streaming) — forced via an explicit ConvPlan with bi < ci."""
    from repro.core.dataflow import ConvPlan
    x = _rand(0, (2, 11, 11, 48))
    f = _rand(1, (3, 3, 48, 40), 0.2)
    b = _rand(2, (40,))
    want = ref.apply_act(ref.conv2d(x, f, stride=2) + b, "relu")
    for fuse in (True, False):
        plan = ConvPlan(case=4, regime="sa_conv", bi=16, bj=16,
                        fuse_taps=fuse, hbm_bytes=0, flops=0, vmem_bytes=0,
                        m=2 * 5 * 5, n=40, k=3 * 3 * 48)
        got = sa_conv_implicit(x, f, b, stride=2, act="relu", plan=plan)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3,
                                   err_msg=f"fuse_taps={fuse}")


def test_tight_budget_plans_streamed_taps_and_kernel_runs_them():
    """A VMEM budget that cannot hold the fused patch tile must yield a
    fuse_taps=False plan, and the kernel must execute it correctly."""
    budget = 1800 * 1024
    plan = plan_conv(1, 20, 20, 64, 3, 3, 128, stride=1, bytes_in=4,
                     bytes_w=4, vmem_budget=budget)
    assert not plan.fuse_taps and plan.vmem_bytes <= budget
    x, f = _rand(0, (1, 20, 20, 64)), _rand(1, (3, 3, 64, 128), 0.1)
    got = sa_conv_implicit(x, f, stride=1, plan=plan)
    np.testing.assert_allclose(got, ref.conv2d(x, f), rtol=2e-3, atol=2e-3)


def test_no_materialized_im2col_on_conv_path(monkeypatch):
    """The forward hot path never touches conv_general_dilated_patches."""
    def boom(*a, **k):
        raise AssertionError("materialized im2col on the CONV hot path")
    monkeypatch.setattr(jax.lax, "conv_general_dilated_patches", boom)
    eng = Engine(backend="pallas")
    x, f = _rand(0, (1, 10, 10, 4)), _rand(1, (3, 3, 4, 8), 0.2)
    got = eng.conv2d(x, f, stride=1, pad=1)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    np.testing.assert_allclose(got, ref.conv2d(xp, f), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# conv planning: policy plumbing (the old conv2d_mpna ignored the engine)
# ---------------------------------------------------------------------------
def test_conv_respects_policy_vmem_budget():
    budget = 1800 * 1024
    eng = Engine(backend="xla",
                 policy=DispatchPolicy(vmem_budget=budget))
    x, f = _rand(0, (1, 20, 20, 64)), _rand(1, (3, 3, 64, 128), 0.1)
    with eng.tracing() as tr:
        eng.conv2d(x, f, name="budgeted")
    plan = tr[0].conv_plan
    assert plan is not None and plan.vmem_bytes <= budget
    default = Engine(backend="xla")
    with default.tracing() as tr2:
        default.conv2d(x, f, name="budgeted")
    assert tr2[0].conv_plan.vmem_bytes > budget  # budget actually binds


def test_forced_regime_policy_reaches_conv_path():
    """A force_regime policy must be visible on CONV dispatches — including
    through the legacy conv2d_mpna shim, which used to bypass the engine.
    (The regime names the array assignment for planning/accounting; the
    implicit-GEMM kernel serves both arrays, as the paper's CONV-capable
    SA-FC does — Sec. IV-B.)"""
    from repro.kernels.conv2d import conv2d_mpna
    x, f = _rand(0, (1, 10, 10, 4)), _rand(1, (3, 3, 4, 8), 0.2)
    forced = Engine(backend="xla",
                    policy=DispatchPolicy(force_regime="sa_fc"))
    with forced.tracing() as tr:
        forced.conv2d(x, f, name="conv")
    assert tr[0].regime == "sa_fc" and tr[0].conv_plan.regime == "sa_fc"
    with forced.tracing() as tr2, forced.activate():
        conv2d_mpna(x, f)                       # shim -> ambient engine
    assert len(tr2) == 1 and tr2[0].regime == "sa_fc"
    assert tr2[0].conv_plan is not None


def test_conv_plan_traffic_bounds():
    plan = plan_conv(2, 31, 31, 96, 5, 5, 256, stride=1,
                     bytes_in=4, bytes_w=4)
    lo = compulsory_conv_bytes(2, 31, 31, 96, 5, 5, 256, stride=1,
                               bytes_in=4, bytes_w=4)
    assert plan.hbm_bytes >= lo
    # the planner counts real NHWC bytes: even one full re-read of the
    # input per CO tile stays far below the patch-matrix blowup
    patch_bytes = 2 * 27 * 27 * 5 * 5 * 96 * 4
    assert plan.hbm_bytes < patch_bytes


# ---------------------------------------------------------------------------
# plan-vs-execution agreement (the silent 512 clamp is gone)
# ---------------------------------------------------------------------------
def test_plan_tiles_capped_at_kernel_maximum():
    for shape in [(600, 640, 1280), (4096, 8192, 8192), (65536, 1024, 640)]:
        p = plan_matmul(*shape, bytes_in=4)
        assert max(p.bm, p.bn, p.bk) <= MAX_TILE, (shape, p)


def test_executed_tiles_equal_plan(monkeypatch):
    """Regression: sa_conv_matmul used to clamp plan tiles to 512 while the
    trace/roofline reported the unclamped plan's traffic."""
    from repro.kernels import sa_conv as sc
    m, n, k = 601, 640, 1283            # fresh shape -> no jit-cache hit
    plan = plan_matmul(m, n, k, bytes_in=4)
    captured = {}
    real = sc.pl.pallas_call

    def spy(kernel, **kw):
        captured["grid"] = kw.get("grid")
        captured["blocks"] = tuple(s.block_shape for s in kw["in_specs"])
        return real(kernel, **kw)

    monkeypatch.setattr(sc.pl, "pallas_call", spy)
    x, w = _rand(0, (m, k)), _rand(1, (k, n), 0.1)
    out = sa_conv_matmul(x, w, plan=plan)
    np.testing.assert_allclose(out, ref.matmul(x, w), rtol=2e-3, atol=2e-3)
    assert captured["grid"] == plan.grid(m, n, k)
    assert captured["blocks"][0] == (plan.bm, plan.bk)
    assert captured["blocks"][1] == (plan.bk, plan.bn)


def test_classify_regime_costed_like_plan():
    """Output bytes enter classification at the same width planning uses —
    near-ridge ops classify to the array they are then planned/rooflined
    as (768^3 sat exactly in the old 2-vs-4-byte disagreement window)."""
    m = n = k = 768
    assert classify_regime(m, n, k) == plan_matmul(m, n, k).regime
    # the parameter is live: the old 2-byte output costing flips it
    assert classify_regime(m, n, k, bytes_out=2) == "sa_conv"
    assert classify_regime(m, n, k, bytes_out=4) == "sa_fc"


# ---------------------------------------------------------------------------
# compiled schedule: conv entries resolved by lookup, not re-planned
# ---------------------------------------------------------------------------
def test_cnn_schedule_conv_entries_and_hits():
    clear_schedule_cache()
    sched = LayerSchedule.compile_cnn("alexnet", batch=2, in_res=67,
                                      width_mult=0.125)
    assert len(sched.conv_entries) == 5 and len(sched) == 3
    # memoized
    assert LayerSchedule.compile_cnn("alexnet", batch=2, in_res=67,
                                     width_mult=0.125) is sched
    params = jax.eval_shape(
        lambda: cnn.init_cnn("alexnet", jax.random.PRNGKey(0), in_res=67,
                             width_mult=0.125))
    eng = Engine(backend="xla").with_schedule(sched)
    x = jax.ShapeDtypeStruct((2, 67, 67, 3), jnp.float32)
    with eng.tracing() as tr:
        jax.eval_shape(lambda p, xv: cnn.cnn_forward("alexnet", p, xv,
                                                     eng=eng), params, x)
    convs = [r for r in tr if r.conv_plan is not None]
    assert len(convs) == 5
    assert all(r.schedule == "hit" for r in tr), tr.summary()
    # the conv+pool pairs rode the fused epilogue (AlexNet: 3 pools)
    assert sum(r.conv_plan.fuse_pool for r in convs) == 3
    # executed tile shapes are the plan's (lookup returns the same object);
    # the key carries the pool request so fused and plain convs of the same
    # geometry cannot collide
    from repro.core.dataflow import PoolSpec
    key = next(iter(sched.conv_entries))
    pool = PoolSpec(key.pool_window, key.pool_stride) \
        if key.pool_window else None
    assert sched.lookup_conv(key.name, key.batch, key.h, key.w, key.ci,
                             key.p, key.q, key.co, key.stride, key.dtype,
                             key.weight_dtype,
                             pool=pool) is sched.conv_entries[key]


def test_schedule_conv_traffic_matches_perf_model():
    """The analytic CONV traffic the roofline/benchmarks report is exactly
    what the compiled schedule commits to."""
    clear_schedule_cache()
    sched = LayerSchedule.compile_cnn("alexnet", batch=1)
    by_name = {k.name: p for k, p in sched.conv_entries.items()}
    rows = pallas_conv_traffic("alexnet", batch=1)
    assert len(rows) == len(by_name) == 5
    for row in rows:
        assert by_name[row.layer] == row.plan
        assert row.plan.hbm_bytes >= row.compulsory_bytes
        assert row.plan.hbm_bytes < row.im2col_bytes


def test_roofline_terms_include_conv_entries():
    from repro.core.roofline import terms_from_schedule
    clear_schedule_cache()
    sched = LayerSchedule.compile_cnn("alexnet", batch=1, in_res=67,
                                      width_mult=0.125)
    t = terms_from_schedule(sched)
    conv_flops = sum(p.flops for p in sched.conv_entries.values())
    fc_flops = sum(p.flops for p in sched.values())
    assert conv_flops > 0 and fc_flops > 0
    assert t.flops_per_chip == pytest.approx(conv_flops + fc_flops)
