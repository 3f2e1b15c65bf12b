"""Batch-amortized SA-FC dataflow: the FC planner (plan_fc / FCPlan), the
batch-tiled weight-streaming kernel, and the engine/schedule/perf-model
plumbing that carries the plan end to end."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.accelerator import TPU_V5E
from repro.core.dataflow import (MAX_TILE, FCPlan, classify_regime,
                                 compulsory_bytes, fc_flip_batch,
                                 fc_vmem_bytes, plan_fc)
from repro.core.engine import DispatchPolicy, Engine
from repro.core.schedule import LayerSchedule
from repro.kernels import ref
from repro.kernels.sa_fc import sa_fc_matmul

RTOL = dict(rtol=3e-4, atol=3e-4)

# AlexNet classifier head, fp32 (the paper's Fig. 6b workload: ~58.6M of
# AlexNet's ~62M weights at weight reuse 1)
FC1 = dict(n=4096, k=9216)


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32) \
        .astype(dtype)


# ---------------------------------------------------------------------------
# planner: amortization, budget, flip batch
# ---------------------------------------------------------------------------
def test_fc_plan_traffic_bounds_and_flops():
    for b in (1, 3, 64, 300):
        p = plan_fc(b, 4096, 9216, bytes_in=4)
        assert p.flops == 2 * b * 4096 * 9216
        # padded traffic is never below half the unpadded compulsory bound
        assert p.hbm_bytes >= compulsory_bytes(b, 4096, 9216, 4) * 0.5
        assert p.case in (1, 2, 3, 4)


def test_fc_plan_weight_amortization_monotone():
    """The headline curve: streamed weight bytes per sample never grows
    with the batch, and one batch tile == one full stream."""
    prev = None
    for b in (1, 4, 16, 64, 256):
        p = plan_fc(b, **FC1, bytes_in=4)
        assert p.weight_passes * p.bb >= min(b, p.bb)
        if prev is not None:
            assert p.weight_bytes_per_sample <= prev + 1e-9
        prev = p.weight_bytes_per_sample


def test_fc_plan_b64_amortizes_at_least_32x():
    """Acceptance: weights-bytes/sample at b=64 <= 1/32 of b=1 for the
    AlexNet head (the planner keeps all 64 samples resident in one batch
    tile, so it is exactly 1/64)."""
    for shape in ((4096, 9216), (4096, 4096), (1000, 4096)):
        n, k = shape
        p1 = plan_fc(1, n, k, bytes_in=4)
        p64 = plan_fc(64, n, k, bytes_in=4)
        assert p64.weight_bytes_per_sample <= p1.weight_bytes_per_sample / 32
        assert p64.bb == 64 and p64.weight_passes == 1


def test_fc_plan_vmem_within_budget_and_tiles_capped():
    for budget in (256 * 1024, 2 * 1024 * 1024, None):
        p = plan_fc(256, **FC1, bytes_in=4, vmem_budget=budget)
        limit = budget if budget is not None else TPU_V5E.vmem_budget
        assert p.vmem_bytes <= limit
        assert max(p.bb, p.bn, p.bk) <= MAX_TILE
        # the plan's own vmem claim is the shared kernel-side formula
        assert p.vmem_bytes == fc_vmem_bytes(p.bb, p.bn, p.bk, bytes_in=4,
                                             bytes_w=4)


def test_fc_plan_tight_budget_shrinks_batch_tile():
    """A VMEM budget that cannot hold the whole batch forces a smaller
    resident batch tile and charges the extra weight passes honestly."""
    wide = plan_fc(256, **FC1, bytes_in=4)
    tight = plan_fc(256, **FC1, bytes_in=4, vmem_budget=400 * 1024)
    assert wide.bb == 256 and wide.weight_passes == 1
    assert tight.bb < wide.bb and tight.weight_passes > 1
    assert tight.weight_hbm_bytes > wide.weight_hbm_bytes
    assert tight.vmem_bytes <= 400 * 1024


def test_fc_plan_impossible_budget_raises():
    from repro.core.dataflow import PlanError
    with pytest.raises(PlanError) as ei:
        plan_fc(16, 256, 256, bytes_in=4, vmem_budget=1024)
    assert ei.value.shape == (16, 256, 256)
    assert ei.value.vmem_budget == 1024
    assert "SA-FC" in str(ei.value)


def test_fc_flip_batch_pinned():
    """The memory-bound -> compute-bound flip is a planner output: for
    AlexNet fc1 in fp32 on the v5e ridge (~240.5 FLOP/B) it sits at
    b=580, and classify_regime flips exactly there."""
    flip = fc_flip_batch(**FC1, bytes_in=4)
    assert flip == 580
    assert classify_regime(flip, FC1["n"], FC1["k"], 4) == "sa_conv"
    assert classify_regime(flip - 1, FC1["n"], FC1["k"], 4) == "sa_fc"
    # the plan carries it, independent of the planning batch
    assert plan_fc(8, **FC1, bytes_in=4).flip_batch == 580
    # int8 weights stream 4x fewer bytes -> the flip comes 4x earlier
    flip8 = fc_flip_batch(**FC1, bytes_in=4, bytes_w=1)
    assert flip8 == 145 and abs(flip8 - flip / 4) <= 1


def test_fc_flip_batch_never_for_tiny_layers():
    # n*k too small for any batch to cross the ridge
    assert fc_flip_batch(64, 64, bytes_in=4) == 0
    assert plan_fc(4, 64, 64, bytes_in=4).flip_batch == 0


# ---------------------------------------------------------------------------
# kernel: batch-tiled grid edge cases
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,k,n,bb", [
    (1, 512, 1024, None),      # b=1, whole-batch tile
    (1, 130, 190, 16),         # b=1 + unaligned k/n
    (5, 300, 257, 16),         # b below one tile, unaligned k/n
    (33, 512, 384, 16),        # b not a multiple of the batch tile
    (64, 1000, 129, 32),       # multiple batch tiles, unaligned n
    (48, 4096, 512, 16),       # deep contraction, 3 batch tiles
])
def test_sa_fc_batch_tiled_sweep(b, k, n, bb):
    x, w = _rand(0, (b, k)), _rand(1, (k, n))
    got = sa_fc_matmul(x, w, act="none", bb=bb, bn=128, bk=128)
    np.testing.assert_allclose(got, ref.gemv(x, w), **RTOL)


@pytest.mark.parametrize("act", ["none", "relu", "leaky_relu", "silu"])
def test_sa_fc_int8_scale_bias_acts(act):
    """int8 weight stream + per-channel scale + bias + every activation
    through the batch-tiled grid (the flush epilogue runs once per
    (batch, N) tile — scale/bias must not re-apply across batch tiles)."""
    x = _rand(0, (40, 300)) * 0.5
    w = _rand(1, (300, 200)) * 0.1
    bias = _rand(2, (200,))
    qt = quant.quantize(w)
    got = sa_fc_matmul(x, qt.q, bias, act=act, bb=16, bn=128, bk=128,
                       w_scale=qt.scale.reshape(1, -1))
    want = ref.matmul_bias_act(x, quant.dequantize(qt, jnp.float32), bias,
                               act=act)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_sa_fc_batch_tiled_equals_whole_batch_resident():
    """Tiling the batch changes traffic, not math: every row's contraction
    order is identical, so the outputs are bitwise equal."""
    x, w = _rand(0, (40, 512)), _rand(1, (512, 256))
    tiled = sa_fc_matmul(x, w, act="relu", bb=16, bn=128, bk=128)
    whole = sa_fc_matmul(x, w, act="relu", bb=None, bn=128, bk=128)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(whole))


def test_sa_fc_vmem_limit_enforced():
    """The kernel refuses block shapes that could never be resident on the
    modeled hardware (previously nothing stopped the caller)."""
    x, w = jnp.zeros((64, 512)), jnp.zeros((512, 512))
    need = fc_vmem_bytes(64, 512, 512, bytes_in=4, bytes_w=4)
    with pytest.raises(ValueError, match="vmem_limit"):
        sa_fc_matmul(x, w, bb=64, bn=512, bk=512, vmem_limit=need - 1)
    # exactly-fitting limit runs
    out = sa_fc_matmul(x, w, bb=64, bn=512, bk=512, vmem_limit=need)
    assert out.shape == (64, 512)


def test_sa_fc_executes_plan_tiles_verbatim(monkeypatch):
    """The PR-2 clamp regression, for FC: the kernel must run the FCPlan's
    (bb, bn, bk) and grid exactly — the plan's hbm/vmem accounting
    describes the executed schedule, not a silently-clamped one."""
    import repro.kernels.sa_fc as sf
    b, n, k = 200, 640, 1280            # fresh shape -> no jit-cache hit
    plan = plan_fc(b, n, k, bytes_in=4, vmem_budget=500 * 1024)
    assert plan.weight_passes > 1          # the batch really is tiled
    captured = {}
    real = sf.pl.pallas_call

    def spy(kernel, **kw):
        captured["grid"] = kw["grid"]
        captured["blocks"] = [s.block_shape for s in kw["in_specs"]]
        return real(kernel, **kw)

    monkeypatch.setattr(sf.pl, "pallas_call", spy)
    x, w = _rand(0, (b, k)), _rand(1, (k, n))
    out = sa_fc_matmul(x, w, bb=plan.bb, bn=plan.bn, bk=plan.bk)
    np.testing.assert_allclose(out, ref.gemv(x, w), **RTOL)
    assert captured["grid"] == plan.grid(b, n, k)
    assert captured["blocks"][0] == (plan.bb, plan.bk)
    assert captured["blocks"][1] == (plan.bk, plan.bn)


# ---------------------------------------------------------------------------
# engine + schedule: the FCPlan rides the dispatch end to end
# ---------------------------------------------------------------------------
def test_engine_fc_dispatch_carries_fc_plan():
    eng = Engine(backend="pallas")
    x, w = _rand(0, (8, 2048)), _rand(1, (2048, 1024)) * 0.1
    with eng.tracing() as tr:
        y = eng.matmul(x, w, act="relu", name="fc1")
    np.testing.assert_allclose(y, ref.matmul_bias_act(x, w, None,
                                                      act="relu"), **RTOL)
    r = tr[0]
    assert r.regime == "sa_fc"
    assert isinstance(r.fc_plan, FCPlan) and r.plan is None
    assert r.fc_plan.vmem_bytes <= eng.policy.effective_vmem_budget


def test_engine_forced_sa_conv_keeps_matmul_plan():
    eng = Engine(policy=DispatchPolicy(force_regime="sa_conv"))
    with eng.tracing() as tr:
        eng.matmul(_rand(0, (8, 256)), _rand(1, (256, 128)), name="op")
    assert tr[0].plan is not None and tr[0].fc_plan is None


def test_engine_fc_grad_flows_through_batch_tiled_kernel():
    """The custom VJP still delivers (dx, dw, db) through the batch-tiled
    forward."""
    eng = Engine(backend="pallas")
    x = _rand(0, (8, 256))
    w = _rand(1, (256, 128)) * 0.1
    b = _rand(2, (128,))
    grads = jax.grad(lambda a, c, d: eng.matmul(a, c, d, act="relu",
                                                name="fc").sum(),
                     argnums=(0, 1, 2))(x, w, b)
    oracle = jax.grad(
        lambda a, c, d: ref.matmul_bias_act(a, c, d, act="relu").sum(),
        argnums=(0, 1, 2))(x, w, b)
    for g, o in zip(grads, oracle):
        np.testing.assert_allclose(g, o, **RTOL)


def test_fc_backward_dx_batch_tiled_under_budget(monkeypatch):
    """The residency invariant holds for the BACKWARD pass too: the
    dx = g @ w^T stream gets its own batch-tiled plan under the same
    vmem_limit — not the legacy whole-batch-resident fallback."""
    import repro.core.engine as E
    calls = []
    real = E.sa_fc_matmul

    def spy(x, w, bias=None, **kw):
        calls.append({"shape": (x.shape, w.shape), "bb": kw.get("bb"),
                      "vmem_limit": kw.get("vmem_limit")})
        return real(x, w, bias, **kw)

    monkeypatch.setattr(E, "sa_fc_matmul", spy)
    budget = 600 * 1024
    eng = Engine(backend="pallas",
                 policy=DispatchPolicy(vmem_budget=budget))
    x = _rand(0, (256, 512))
    w = _rand(1, (512, 384)) * 0.1
    gx = jax.grad(lambda a: eng.matmul(a, w, act="relu",
                                       name="fc").sum())(x)
    oracle = jax.grad(
        lambda a: ref.matmul_bias_act(a, w, None, act="relu").sum())(x)
    np.testing.assert_allclose(gx, oracle, **RTOL)
    # forward, recompute and dx all ran the sa_fc kernel with an explicit
    # batch tile and the policy budget enforced
    assert len(calls) >= 3
    assert all(c["bb"] is not None and c["vmem_limit"] == budget
               for c in calls)
    dx_call = [c for c in calls if c["shape"][1] == (384, 512)]
    assert dx_call and dx_call[0]["bb"] < 256      # batch really tiled


def test_cnn_schedule_fc_entries_are_fc_plans_and_hit():
    sched = LayerSchedule.compile_cnn("alexnet", batch=4, in_res=67,
                                      width_mult=0.125)
    fc_keys = [key for key in sched if key.name.startswith("fc")]
    assert len(fc_keys) == 3
    assert all(isinstance(sched[key], FCPlan) for key in fc_keys)
    # an engine carrying the schedule resolves FC layers by lookup and
    # executes the looked-up batch-tiled plan
    from repro.models import cnn
    params = cnn.init_cnn("alexnet", jax.random.PRNGKey(0), in_res=67,
                          width_mult=0.125)
    x = _rand(1, (4, 67, 67, 3))
    eng = Engine(backend="pallas").with_schedule(sched)
    with eng.tracing() as tr:
        y = cnn.cnn_forward("alexnet", params, x, eng=eng)
    y_ref = cnn.cnn_forward("alexnet", params, x, backend="xla")
    np.testing.assert_allclose(y, y_ref, **RTOL)
    fc_recs = [r for r in tr if r.name.startswith("fc")]
    assert fc_recs and all(r.schedule == "hit" for r in fc_recs)
    assert all(r.fc_plan is not None for r in fc_recs)


def test_schedule_table_renders_fc_plans():
    sched = LayerSchedule.compile_cnn("alexnet", batch=4, in_res=67,
                                      width_mult=0.125)
    table = sched.table()
    assert "bb=" in table and "wstream" in table


# ---------------------------------------------------------------------------
# perf model + roofline: planner-vs-compulsory bytes/sample reporting
# ---------------------------------------------------------------------------
def test_pallas_fc_traffic_amortization_curve():
    from repro.core.perf_model import pallas_fc_traffic
    rows1 = pallas_fc_traffic("alexnet", batch=1)
    rows64 = pallas_fc_traffic("alexnet", batch=64)
    assert [r.layer for r in rows1] == ["fc1", "fc2", "fc3"]
    s1 = sum(r.weight_bytes_per_sample for r in rows1)
    s64 = sum(r.weight_bytes_per_sample for r in rows64)
    assert s64 <= s1 / 32                       # acceptance headline
    # at batch 1 the planner streams exactly one compulsory pass
    for r in rows1:
        assert r.weight_hbm_bytes >= r.compulsory_weight_bytes
        assert r.plan.weight_passes == 1


def test_fc_batch_traffic_from_schedule():
    from repro.core.roofline import fc_batch_traffic_from_schedule
    sched = LayerSchedule.compile_cnn("alexnet", batch=16, in_res=67,
                                      width_mult=0.125)
    rep = fc_batch_traffic_from_schedule(sched)
    assert set(rep) == {"fc1", "fc2", "fc3"}
    for row in rep.values():
        assert row["batch"] == 16
        assert row["weight_passes"] >= 1
        assert row["weight_bytes_per_sample"] >= \
            row["compulsory_weight_bytes_per_sample"] - 1e-9
        assert "flip_batch" in row
