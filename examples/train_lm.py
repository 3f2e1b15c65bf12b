"""End-to-end driver: train a ~100M-param LM for a few hundred steps on the
synthetic pipeline, with checkpointing + auto-resume.

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--tiny]

--tiny shrinks the model for a fast smoke run (CI uses it); the default
is a ~100M decoder (12L x 768, the assignment's end-to-end train scale).
"""
import argparse

from repro.configs.base import ModelConfig, TrainConfig
from repro.train import trainer


def model_100m() -> ModelConfig:
    # 12L d768 12H ff3072 vocab 32000 ~= 110M params (GPT-2-small class)
    return ModelConfig(name="lm-100m", family="dense", n_layers=12,
                       d_model=768, n_heads=12, n_kv_heads=12, d_ff=3072,
                       vocab_size=32_000, head_dim=64,
                       param_dtype="float32", compute_dtype="float32")


def model_tiny() -> ModelConfig:
    return ModelConfig(name="lm-tiny", family="dense", n_layers=2,
                       d_model=128, n_heads=4, n_kv_heads=4, d_ff=512,
                       vocab_size=1024, head_dim=32,
                       param_dtype="float32", compute_dtype="float32")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = model_tiny() if args.tiny else model_100m()
    print(f"[train_lm] {cfg.name}: {cfg.n_params()/1e6:.1f}M params")
    tc = TrainConfig(global_batch=args.batch, seq_len=args.seq,
                     total_steps=args.steps, lr=3e-4, warmup_steps=20,
                     microbatch=max(1, args.batch // 2), remat="block",
                     grad_compress="none")
    report = trainer.run(cfg, tc, ckpt_dir=args.ckpt_dir, ckpt_every=100,
                         log_every=min(10, max(1, args.steps - 1)))
    print(f"[train_lm] done: loss {report.losses[0]:.3f} -> "
          f"{report.final_loss:.3f} over {report.steps_run} steps "
          f"(resumed_from={report.resumed_from})")
    assert report.final_loss < report.losses[0], "loss did not improve"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
