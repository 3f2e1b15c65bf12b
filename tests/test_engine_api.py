"""The explicit Engine / DispatchPolicy / LayerSchedule API.

Covers the redesign's contracts: schedule compilation is deterministic and
memoized; policies are pluggable (force a regime and see it in the trace);
int8 QTensor weights reach the Pallas kernels un-dequantized with the
scale fused in the epilogue; the bias-less pallas VJP is structurally
clean; output dtype is applied exactly once."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, TrainConfig
from repro.core import quant
from repro.core.engine import (DispatchPolicy, DispatchRecord, DispatchTrace,
                               Engine, current, default_engine,
                               dispatch_trace, matmul)
from repro.core.perf_model import offline_layer_schedule
from repro.core.roofline import terms_from_schedule
from repro.core.schedule import LayerSchedule, OpKey, clear_schedule_cache
from repro.kernels import ref

CFG = ModelConfig(name="api", family="dense", n_layers=2, d_model=128,
                  n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=512,
                  head_dim=32, param_dtype="float32",
                  compute_dtype="float32")


# ---------------------------------------------------------------------------
# LayerSchedule: compile once, inspect, reuse
# ---------------------------------------------------------------------------
def test_schedule_compile_is_memoized():
    s1 = LayerSchedule.compile(CFG, "decode", batch=4, max_seq=64)
    s2 = LayerSchedule.compile(CFG, "decode", batch=4, max_seq=64)
    assert s1 is s2                     # the cached object itself
    assert len(s1) > 0
    assert all(isinstance(k, OpKey) for k in s1)


def test_schedule_deterministic_across_cache_clears():
    s1 = LayerSchedule.compile(CFG, "train", batch=4, seq=32)
    clear_schedule_cache()
    s2 = LayerSchedule.compile(CFG, "train", batch=4, seq=32)
    assert s1 is not s2
    assert s1 == s2                     # same config -> identical schedule


def test_schedule_phases_differ():
    tr = LayerSchedule.compile(CFG, "train", batch=8, seq=64)
    de = LayerSchedule.compile(CFG, "decode", batch=8, max_seq=64)
    # decode ops are GEMVs (m = batch); train ops see batch*seq rows
    assert {k.m for k in de} == {8} or 8 in {k.m for k in de}
    assert max(k.m for k in tr) > max(k.m for k in de)


def test_engine_consumes_schedule_with_hits():
    sched = LayerSchedule.compile(CFG, "decode", batch=4, max_seq=64)
    eng = Engine(schedule=sched)
    from repro.models import transformer as T
    from repro.serve import kvcache as KC
    from repro.serve.serve_step import decode_step
    params = jax.eval_shape(lambda: T.init_params(CFG, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: KC.init_cache(CFG, 4, 64,
                                                 dtype=jnp.bfloat16))
    tok = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    with eng.tracing() as tr, eng.activate():
        jax.eval_shape(lambda p, c, t: decode_step(CFG, p, c, t,
                                                   jnp.int32(7)),
                       params, cache, tok)
    mm = [r for r in tr if r.regime in ("sa_conv", "sa_fc")]
    assert mm and all(r.schedule == "hit" for r in mm)


def test_serve_engine_consumes_layer_schedule():
    from repro.models import transformer as T
    from repro.serve.engine import Request, ServeEngine
    params = T.init_params(CFG, jax.random.PRNGKey(0))
    eng = Engine()
    seng = ServeEngine(CFG, params, batch_size=2, max_seq=48, engine=eng)
    assert isinstance(seng.decode_schedule, LayerSchedule)
    rng = np.random.default_rng(0)
    with eng.tracing() as tr:
        for uid in range(2):
            seng.submit(Request(uid=uid,
                                prompt=rng.integers(0, 512, size=8,
                                                    dtype=np.int64)
                                .astype(np.int32),
                                max_new=4))
        done = seng.run()
    assert len(done) == 2
    hits = [r for r in tr if r.schedule == "hit"]
    assert hits, "serve execution should resolve plans from the schedule"


def test_train_step_consumes_layer_schedule():
    from repro.train import train_step as TS
    tc = TrainConfig(global_batch=4, seq_len=16, total_steps=1)
    eng = Engine()
    step = TS.make_train_step(CFG, tc, engine=eng)
    params, opt, cs = TS.init_train_state(CFG, tc, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 512)
    with eng.tracing() as tr:
        params, opt, cs, m = step(params, opt, cs, {"tokens": tokens})
    assert np.isfinite(float(m["loss"]))
    hits = [r for r in tr if r.schedule == "hit"]
    assert hits, "train execution should resolve plans from the schedule"
    # the schedule itself is memoized for the step's shape
    s1 = LayerSchedule.compile(CFG, "train", batch=4, seq=16,
                               policy=eng.policy, params=params)
    s2 = LayerSchedule.compile(CFG, "train", batch=4, seq=16,
                               policy=eng.policy, params=params)
    assert s1 is s2


# ---------------------------------------------------------------------------
# DispatchPolicy: pluggable classification
# ---------------------------------------------------------------------------
def test_policy_force_regime_observed_in_trace():
    x = jnp.zeros((16384, 4096), jnp.bfloat16)     # firmly compute-bound
    w = jnp.zeros((4096, 4096), jnp.bfloat16)
    base = Engine()
    with base.tracing() as tr:
        base.matmul(x, w, name="op")
    assert tr[0]["regime"] == "sa_conv"
    forced = Engine(policy=DispatchPolicy(force_regime="sa_fc"))
    with forced.tracing() as tr:
        forced.matmul(x, w, name="op")
    assert tr[0]["regime"] == "sa_fc"


def test_policy_per_op_override():
    pol = DispatchPolicy(overrides=(("special", "sa_fc"),))
    eng = Engine(policy=pol)
    x = jnp.zeros((16384, 4096), jnp.bfloat16)
    w = jnp.zeros((4096, 4096), jnp.bfloat16)
    with eng.tracing() as tr:
        eng.matmul(x, w, name="special")
        eng.matmul(x, w, name="plain")
    assert tr[0].regime == "sa_fc" and tr[1].regime == "sa_conv"


def test_int8_weight_bytes_flip_regime():
    """1 byte/weight halves the dominant k*n byte term: an op just below
    the ridge with bf16 weights crosses it with int8 weights."""
    x = jnp.zeros((150, 4096), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(0), (4096, 4096)) * 0.02
    eng = Engine()
    with eng.tracing() as tr:
        eng.matmul(x, w.astype(jnp.bfloat16), name="op")
        eng.matmul(x, quant.quantize(w), name="op")
    assert tr[0].regime == "sa_fc"
    assert tr[1].regime == "sa_conv"
    assert tr[1].weight_dtype == "int8"


# ---------------------------------------------------------------------------
# int8 QTensor: un-dequantized into the kernel, scale fused in the epilogue
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_qtensor_matmul_matches_dequant_oracle(backend):
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 256), jnp.float32) * 0.5
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 128), jnp.float32) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(3), (128,), jnp.float32)
    qt = quant.quantize(w)
    eng = Engine(backend=backend)
    with eng.tracing() as tr:
        y = eng.matmul(x, qt, b, act="relu", name="q")
    oracle = ref.matmul_bias_act(x, quant.dequantize(qt, jnp.float32), b,
                                 act="relu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)
    assert tr[0].weight_dtype == "int8"


def test_qtensor_reaches_pallas_kernel_undequantized(monkeypatch):
    """The int8 array itself (not a widened copy) must be the kernel's
    weight operand."""
    import repro.core.engine as E
    seen = {}
    real = E._pallas_matmul

    def spy(x2d, w, bias, act, regime, **kw):
        seen["w_dtype"] = w.dtype
        seen["w_scale"] = kw.get("w_scale") is not None
        return real(x2d, w, bias, act, regime, **kw)

    monkeypatch.setattr(E, "_pallas_matmul", spy)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 256), jnp.float32)
    qt = quant.quantize(
        jax.random.normal(jax.random.PRNGKey(2), (256, 128)) * 0.1)
    Engine(backend="pallas").matmul(x, qt)
    assert seen["w_dtype"] == jnp.int8
    assert seen["w_scale"] is True


# ---------------------------------------------------------------------------
# VJP structure + single cast
# ---------------------------------------------------------------------------
def test_qtensor_pallas_grad_flows_through_int8():
    """Gradients w.r.t. activations (and bias) flow through a quantized
    pallas matmul; the int8 weights stay frozen constants."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 256), jnp.float32) * 0.5
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 128),
                          jnp.float32) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(2), (128,), jnp.float32)
    qt = quant.quantize(w)
    wd = quant.dequantize(qt, jnp.float32)
    eng = Engine(backend="pallas")
    gx, gb = jax.grad(lambda a, c: eng.matmul(a, qt, c, act="relu").sum(),
                      argnums=(0, 1))(x, b)
    gx_r, gb_r = jax.grad(
        lambda a, c: jax.nn.relu(a @ wd + c).sum(), argnums=(0, 1))(x, b)
    np.testing.assert_allclose(gx, gx_r, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(gb, gb_r, rtol=3e-4, atol=3e-4)


def test_shared_engine_tracing_is_thread_isolated():
    """tracing() on one shared Engine must keep per-thread records."""
    import threading
    shared = Engine()
    x = jnp.zeros((4, 256), jnp.bfloat16)
    w = jnp.zeros((256, 128), jnp.bfloat16)
    results = {}
    start = threading.Barrier(2)

    def worker(tag, count):
        start.wait()
        with shared.tracing() as tr:
            for i in range(count):
                shared.matmul(x, w, name=f"{tag}{i}")
        results[tag] = [r.name for r in tr]

    threads = [threading.Thread(target=worker, args=("a", 5)),
               threading.Thread(target=worker, args=("b", 8))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results["a"] == [f"a{i}" for i in range(5)]
    assert results["b"] == [f"b{i}" for i in range(8)]
    assert shared.trace is None


def test_biasless_pallas_vjp_structurally_clean():
    """grad through a bias-less pallas matmul returns exactly (dx, dw) —
    no sentinel bias tangent — and matches the oracle."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 48), jnp.float32) * 0.1
    eng = Engine(backend="pallas")
    gx, gw = jax.grad(lambda a, b: eng.matmul(a, b, act="relu").sum(),
                      argnums=(0, 1))(x, w)
    gx2, gw2 = jax.grad(
        lambda a, b: ref.matmul_bias_act(a, b, None, act="relu").sum(),
        argnums=(0, 1))(x, w)
    assert gx.shape == x.shape and gw.shape == w.shape
    np.testing.assert_allclose(gx, gx2, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(gw, gw2, rtol=3e-4, atol=3e-4)


def test_bias_pallas_vjp_matches_oracle():
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 48), jnp.float32) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(2), (48,), jnp.float32)
    eng = Engine(backend="pallas")
    grads = jax.grad(lambda a, c, d: eng.matmul(a, c, d, act="relu").sum(),
                     argnums=(0, 1, 2))(x, w, b)
    oracle = jax.grad(
        lambda a, c, d: ref.matmul_bias_act(a, c, d, act="relu").sum(),
        argnums=(0, 1, 2))(x, w, b)
    for g, o in zip(grads, oracle):
        np.testing.assert_allclose(g, o, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_out_dtype_cast_exactly_once(backend):
    """out_dtype=f32 from bf16 operands must not round-trip through bf16
    (the old double-cast path did on the pallas backend)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 256)).astype(jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(1), (256, 128)) * 0.1
         ).astype(jnp.bfloat16)
    eng = Engine(backend=backend)
    y = eng.matmul(x, w, out_dtype=jnp.float32)
    assert y.dtype == jnp.float32
    exact = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    # f32 accumulator delivered at f32: only accumulation-order noise
    np.testing.assert_allclose(np.asarray(y), np.asarray(exact),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# shims + trace structure
# ---------------------------------------------------------------------------
def test_shims_route_to_default_engine():
    assert current() is default_engine()
    x = jnp.zeros((4, 4096), jnp.bfloat16)
    w = jnp.zeros((4096, 4096), jnp.bfloat16)
    with dispatch_trace() as tr:
        matmul(x, w, name="op")
    assert isinstance(tr, DispatchTrace)
    assert isinstance(tr[0], DispatchRecord)
    assert tr[0]["regime"] == "sa_fc"       # dict-style access still works
    assert tr.counts() == {"sa_fc": 1}


def test_dispatch_trace_shim_is_thread_isolated():
    """Concurrent dispatch_trace() users must not share or clobber each
    other's traces (the old _EngineState thread-local guarantee)."""
    import threading
    x = jnp.zeros((4, 256), jnp.bfloat16)
    w = jnp.zeros((256, 128), jnp.bfloat16)
    results = {}
    start = threading.Barrier(2)

    def worker(tag, count):
        start.wait()
        with dispatch_trace() as tr:
            for i in range(count):
                matmul(x, w, name=f"{tag}{i}")
        results[tag] = [r.name for r in tr]

    threads = [threading.Thread(target=worker, args=("a", 5)),
               threading.Thread(target=worker, args=("b", 7))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results["a"] == [f"a{i}" for i in range(5)]
    assert results["b"] == [f"b{i}" for i in range(7)]
    assert default_engine().trace is None


def test_activation_stack_nests():
    e1, e2 = Engine(), Engine(backend="pallas")
    with e1.activate():
        assert current() is e1
        with e2.activate():
            assert current() is e2
        assert current() is e1
    assert current() is default_engine()


# ---------------------------------------------------------------------------
# offline twins: ASIC schedule table + schedule roofline
# ---------------------------------------------------------------------------
def test_offline_layer_schedule_routes_conv_and_fc():
    table = offline_layer_schedule("alexnet")
    convs = [a for a in table if a.layer.startswith("conv")]
    fcs = [a for a in table if a.layer.startswith("fc")]
    assert convs and all(a.array == "sa_conv" for a in convs)
    assert fcs and all(a.array == "sa_fc" for a in fcs)
    assert all(a.case in (1, 2, 3, 4) for a in table)


def test_terms_from_schedule_consistent():
    sched = LayerSchedule.compile(CFG, "train", batch=4, seq=32)
    t = terms_from_schedule(sched)
    assert t.flops_per_chip == sum(p.flops for p in sched.values())
    assert t.hbm_bytes_per_chip > 0
    assert t.memory_s() > 0 and t.compute_s() > 0


# ---------------------------------------------------------------------------
# the platform decides: interpret mode and the planner's chip
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,interpreted", [("cpu", True),
                                                 ("tpu", False)])
def test_platform_decides_interpret_mode(monkeypatch, backend, interpreted):
    from repro.kernels.geometry import interpret_mode

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert interpret_mode() is interpreted


def test_engine_plans_for_the_attached_chip(monkeypatch):
    """Off the chip the planner targets the modeled v5e; on a TPU it looks
    the chip up by ``device_kind``, and an unknown kind is an error."""
    from repro.core.accelerator import TPU_V5E, chip_for

    assert Engine().policy.chip is TPU_V5E
    assert chip_for("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="TPU v4"):
        chip_for("TPU v4")
    # a TPU backend whose device kind has no constants (here: the CPU's)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="no planner constants"):
        Engine(backend="pallas")
    assert Engine(backend="pallas", chip=TPU_V5E).policy.chip is TPU_V5E
