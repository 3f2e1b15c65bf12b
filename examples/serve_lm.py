"""Serve a small LM with batched requests through the ServeEngine.

Decode is the SA-FC regime (per-step weight reuse = active batch slots);
the engine keeps slots full, which is the software analogue of MPNA's
time-multiplexed second array.

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro.configs.base import ATTN_GLOBAL, ATTN_LOCAL, ModelConfig
from repro.core.engine import Engine
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine


def main() -> None:
    cfg = ModelConfig(name="serve-demo", family="dense", n_layers=4,
                      d_model=256, n_heads=8, n_kv_heads=4, d_ff=1024,
                      vocab_size=4096, head_dim=32,
                      layer_pattern=(ATTN_LOCAL, ATTN_GLOBAL),
                      sliding_window=64, param_dtype="float32",
                      compute_dtype="float32")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    print(f"[serve_lm] {cfg.name}: {cfg.n_params()/1e6:.1f}M params, "
          f"local:global attention with ring KV cache")

    exec_engine = Engine()
    eng = ServeEngine(cfg, params, batch_size=4, max_seq=256,
                      engine=exec_engine)
    print(f"[serve_lm] compiled decode LayerSchedule: "
          f"{len(eng.decode_schedule)} ops, all "
          f"{set(p.regime for p in eng.decode_schedule.values())}")
    rng = np.random.default_rng(0)
    for uid in range(8):
        prompt = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
        eng.submit(Request(uid=uid, prompt=prompt, max_new=16))

    with exec_engine.tracing() as trace:
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0

    toks = sum(len(r.output) for r in done)
    decode_ops = trace.by_regime("sa_fc")
    hits = [t for t in trace if t.schedule == "hit"]
    print(f"[serve_lm] {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s on CPU)")
    print(f"[serve_lm] engine dispatch: {len(decode_ops)} matmuls routed "
          f"to the SA-FC (weight-streaming) regime during decode; "
          f"{len(hits)} plan lookups served by the compiled schedule")
    for r in done[:3]:
        print(f"  req {r.uid}: {r.output[:8].tolist()}...")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
