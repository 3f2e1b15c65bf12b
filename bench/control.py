"""The precision control: the reference put in the program's place at the
next precision below the one the configuration states, and judged by the
run's own verdict (``cell.verdict``) with the configuration's limit.  It
has to come out not correct, or the limit cannot tell a lower-precision
program from a sound one.

The control's answers take the place of the served answers of every image
of a seed's pool:

* ``high``: the reference at ``Precision.HIGH`` (three bf16 passes), the
  step below float32 at ``HIGHEST``;
* ``int4``: for an int8 configuration, the weights requantized to
  symmetric per-channel int4 (``[-7, 7]``), the step below int8.

    python3 bench/control.py --config alexnet --seeds 11,12,13

prints one JSON line per seed and variant, with ``correct`` and the
numbers compared beside their limits, and exits non-zero when any control
comes out correct.  It needs no window; the benchmark's own runs never run
it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _int4(params: list[dict]) -> list[dict]:
    import jax.numpy as jnp

    out = []
    for p in params:
        d = dict(p)
        for k in ("f", "w"):
            if k in d:
                w = d[k]
                amax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)),
                               keepdims=True)
                s = jnp.maximum(amax, 1e-8) / 7.0
                d[k] = jnp.clip(jnp.round(w / s), -7, 7) * s
        out.append(d)
    return out


def readings(cfg: dict, seed: int, pool: int) -> dict[str, tuple[bool, dict]]:
    """Each control variant's ``(correct, checks)`` for ``seed``, as a
    run decides them."""
    import numpy as np

    from bench import cell, reference, weights

    params = weights.make(cfg, seed)
    images = weights.images(cfg, seed, pool)
    ref_params = weights.for_reference(params)
    variants = {"high": (ref_params, "high")}
    if cfg["weights"] == "int8":
        variants["int4"] = (_int4(ref_params), "highest")
    out = {}
    for name, (p, precision) in variants.items():
        got = list(reference.logits(cfg["layers"], p, images,
                                    precision=precision))
        out[name] = cell.verdict(cfg, params, images, np.arange(pool), got)
    return out


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT)]
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--pool", type=int, default=0,
                    help="images per seed (default: the bulk mix's pool)")
    args = ap.parse_args(argv)

    from bench import cell, traffic
    cell.enable_compile_cache(ROOT)
    cfg = json.loads((ROOT / "bench" / "configs" /
                      f"{args.config}.json").read_text())
    pool = args.pool or traffic.load(
        ROOT / "bench" / "traffic" / "bulk.json").pool
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, (correct, checks) in readings(cfg, seed, pool).items():
            passed += correct
            print(json.dumps({"config": args.config, "seed": seed,
                              "control": name, "correct": correct,
                              "checks": checks}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
