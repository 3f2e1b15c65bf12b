"""Serving launcher: batched requests against a (reduced) assigned arch.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-27b \
        --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import reduced
from repro.configs.registry import all_lm_configs
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=sorted(all_lm_configs()))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()

    cfg = reduced(all_lm_configs()[args.arch], param_dtype="float32",
                  compute_dtype="float32")
    if cfg.enc_dec or cfg.vision_tokens:
        raise SystemExit("multimodal serving demo: use examples/serve_lm.py "
                         "with the stubbed frontend inputs")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_size=args.batch_size,
                      max_seq=args.max_seq)
    rng = np.random.default_rng(0)
    for uid in range(args.requests):
        eng.submit(Request(uid=uid,
                           prompt=rng.integers(
                               0, cfg.vocab_size, 8).astype(np.int32),
                           max_new=args.max_new))
    done = eng.run()
    for r in done:
        print(f"req {r.uid}: {r.output.tolist()}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
