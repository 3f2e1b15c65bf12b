"""Quickstart: the explicit MPNA Engine + LayerSchedule API in 90 seconds.

Shows the paper's two dataflows (SA-CONV weight-stationary / SA-FC
weight-streaming Pallas kernels, interpret mode on CPU), the pluggable
arithmetic-intensity dispatch policy, a compiled per-model LayerSchedule
(the paper's offline per-layer schedule table), int8 weights streamed
un-dequantized into the kernel, and one training step driven by the same
engine.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, TrainConfig
from repro.core import dataflow, quant
from repro.core.engine import DispatchPolicy, Engine
from repro.core.schedule import LayerSchedule
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.train import train_step as TS

CFG = ModelConfig(name="quick", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, param_dtype="float32",
                  compute_dtype="float32")


def main():
    print("== 1. heterogeneous dispatch (paper Sec. IV) ==")
    for name, (m, n, k) in {
            "prefill matmul": (8192, 4096, 4096),
            "decode GEMV  ": (8, 4096, 4096)}.items():
        plan = dataflow.plan_matmul(m, n, k)
        print(f"  {name}: ({m}x{k})@({k}x{n}) -> {plan.regime:8s} "
              f"case {plan.case}, tile ({plan.bm},{plan.bn},{plan.bk}), "
              f"planned HBM {plan.hbm_bytes/2**20:.0f} MiB "
              f"(compulsory {dataflow.compulsory_bytes(m,n,k)/2**20:.0f})")

    print("\n== 2. explicit Engine: both dataflows, same operator ==")
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 512), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
    pal = Engine(backend="pallas")
    xla = Engine(backend="xla")
    with pal.tracing() as tr:
        y_pal = pal.matmul(x, w, act="relu")
    y_ref = xla.matmul(x, w, act="relu")
    np.testing.assert_allclose(y_pal, y_ref, rtol=3e-5, atol=3e-5)
    print(f"  pallas({tr[0].regime}) == oracle: "
          f"max|diff| = {float(jnp.max(jnp.abs(y_pal - y_ref))):.2e}")
    forced = Engine(policy=DispatchPolicy(force_regime="sa_conv"))
    with forced.tracing() as tr2:
        forced.matmul(x, w)
    print(f"  pluggable policy: force_regime -> {tr2[0].regime}")

    print("\n== 3. int8 weights, un-dequantized into the kernel ==")
    qt = quant.quantize(w)
    with pal.tracing() as tr:
        yq = pal.matmul(x, qt, name="w8")
    yf = xla.matmul(x, w)
    err = float(jnp.linalg.norm(yq - yf) / jnp.linalg.norm(yf))
    print(f"  weight stream dtype={tr[0].weight_dtype} "
          f"(scale fused in the kernel epilogue), rel err {err:.4f}")

    print("\n== 4. compiled per-model LayerSchedule (paper Sec. V) ==")
    sched = LayerSchedule.compile(CFG, "decode", batch=4, max_seq=64)
    again = LayerSchedule.compile(CFG, "decode", batch=4, max_seq=64)
    print(f"  memoized: second compile returns the same object "
          f"-> {sched is again}")
    print("  " + sched.table().replace("\n", "\n  "))

    print("\n== 5. one LM train step through the engine+schedule ==")
    tc = TrainConfig(global_batch=4, seq_len=32, total_steps=3)
    eng = Engine()
    step = jax.jit(TS.make_train_step(CFG, tc, engine=eng))
    params, opt, cs = TS.init_train_state(CFG, tc, jax.random.PRNGKey(0))
    data = SyntheticLM(DataConfig(CFG.vocab_size, 32, 4), CFG)
    for i in range(3):
        params, opt, cs, m = step(params, opt, cs, data.batch_at(i))
        print(f"  step {i}: loss {float(m['loss']):.4f}")
    print("done.")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
