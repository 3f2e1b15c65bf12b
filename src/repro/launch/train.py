"""Training launcher.

On real hardware this runs under `jax.distributed.initialize()` with the
production mesh; on this container it drives the same code path on the
local device mesh.  The dry-run (launch/dryrun.py) is the multi-pod proof;
this launcher is the single-process executable counterpart.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b \
        --steps 20 --reduced --batch 8 --seq 64
"""
from __future__ import annotations

import argparse

import jax

from repro.configs.base import TrainConfig, reduced
from repro.configs.registry import all_lm_configs
from repro.train import trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=sorted(all_lm_configs()))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = all_lm_configs()[args.arch]
    if args.reduced:
        cfg = reduced(cfg, param_dtype="float32", compute_dtype="float32")
    print(f"[train] {cfg.name}: {cfg.n_params()/1e6:.1f}M params "
          f"({len(jax.devices())} devices)")
    tc = TrainConfig(global_batch=args.batch, seq_len=args.seq,
                     total_steps=args.steps, lr=args.lr,
                     microbatch=args.microbatch,
                     grad_compress=args.grad_compress, remat="block")
    rep = trainer.run(cfg, tc, ckpt_dir=args.ckpt_dir, log_every=10)
    print(f"[train] loss {rep.losses[0]:.4f} -> {rep.final_loss:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
