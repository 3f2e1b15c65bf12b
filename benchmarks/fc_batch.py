"""Batch-amortized SA-FC benchmark — the machine-readable perf trajectory
for the paper's Fig. 7D/Fig. 8 weight-streaming dataflow.

Per-sample FC weight reuse is 1 (paper Sec. V-A): at batch 1 every request
re-streams AlexNet's ~58.6M-weight classifier head from HBM, which is why
the FC stack dominates serving traffic.  The batch-tiled SA-FC kernel
streams each weight byte once per resident **batch tile**, so
weights-bytes/sample falls ~B-fold until the planner's VMEM budget caps
the tile.  This benchmark records both sides of that story:

* **planner** — the real AlexNet classifier head (fc1 9216x4096,
  fc2 4096x4096, fc3 4096x1000, fp32) at b in {1, 4, 16, 64, 256}:
  per-layer and stack weights-bytes/sample (planner vs. compulsory), the
  amortized arithmetic intensity, and the planner-pinned ``flip_batch``
  at which each layer would stop being memory-bound;
* **wall** — interleaved-median wall-clock (benchmarks/timing.py, the
  shared estimator: wall A/B on this container is +-2x noisy at ms scale)
  of the batched head forward vs. one single-sample forward per request,
  on a width-scaled head (interpret-mode Pallas at full fc1 size is
  minutes per call on CPU; the batch-amortization *mechanism* is
  width-independent).

Writes ``BENCH_fc_batch.json`` so the trajectory is diffable across PRs:

    PYTHONPATH=src python benchmarks/fc_batch.py --fast --out BENCH_fc_batch.json
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

try:                                    # package import (benchmarks.run)
    from benchmarks.timing import interleaved_medians, \
        raise_on_failed_checks, run_emit_cli, seeded_payloads
except ImportError:                     # direct script execution
    from timing import interleaved_medians, raise_on_failed_checks, \
        run_emit_cli, seeded_payloads

Row = tuple[str, float, str]

#: Serving batches the planner section sweeps (real AlexNet head shapes).
PLANNER_BATCHES = (1, 4, 16, 64, 256)

#: (net, width_mult, batches, reps, trials) per tier for the wall-clock
#: section.  Widths keep interpret-mode CPU wall in CI-smoke territory;
#: the planner section always runs the full-size head (planning is
#: analytic and costs microseconds).
WALL_CONFIGS = {
    "fast": [("alexnet", 1 / 16, (1, 4, 16), 3, 5)],
    "full": [("alexnet", 0.25, (1, 4, 16, 64, 256), 2, 5),
             ("alexnet", 1.0, (1, 4, 16, 64), 1, 3)],
}


def planner_section(batches=PLANNER_BATCHES, *, bytes_in: int = 4,
                    vmem_budget=None) -> dict:
    """Weights-bytes/sample amortization curve of the real AlexNet head."""
    from repro.core.perf_model import pallas_fc_traffic

    per_batch = {}
    flip = {}
    for b in batches:
        rows = pallas_fc_traffic("alexnet", batch=b, bytes_in=bytes_in,
                                 vmem_budget=vmem_budget)
        layers = []
        for r in rows:
            layers.append({
                "layer": r.layer,
                "batch_tile": r.plan.bb,
                "weight_passes": r.plan.weight_passes,
                "weight_bytes_per_sample": int(r.weight_bytes_per_sample),
                "compulsory_weight_bytes_per_sample":
                    round(r.compulsory_weight_bytes_per_sample, 1),
                "hbm_bytes": int(r.plan.hbm_bytes),
                "amortized_intensity": round(r.plan.arithmetic_intensity, 2),
                "regime": r.plan.regime,
            })
            flip[r.layer] = r.plan.flip_batch
        per_batch[str(b)] = {
            "layers": layers,
            "stack_weight_bytes_per_sample":
                int(sum(r.weight_bytes_per_sample for r in rows)),
        }
    b0, bref = str(batches[0]), "64" if "64" in per_batch else str(batches[-1])
    amort = (per_batch[b0]["stack_weight_bytes_per_sample"]
             / per_batch[bref]["stack_weight_bytes_per_sample"])
    return {"net": "alexnet", "bytes_in": bytes_in,
            "vmem_budget": vmem_budget, "batches": list(batches),
            "per_batch": per_batch,
            "flip_batch": flip,
            f"stack_amortization_b{bref}_vs_b{b0}": round(amort, 2)}


def wall_section(net: str, width_mult: float, batches, *,
                 reps: int, trials: int) -> dict:
    """Interleaved-median per-sample wall: one batched head forward of b
    samples vs. the b single-sample forwards unbatched serving would
    issue (one per request — the single-sample cost is batch-independent,
    so it is measured once per trial and interleaved with every batched
    variant)."""
    from repro.core.engine import Engine
    from repro.models import cnn

    import numpy as np

    head = cnn.fc_head(net, width_mult=width_mult)
    params = cnn.init_fc_head(head, jax.random.PRNGKey(0))
    eng = Engine(backend="pallas")
    k0 = head[0][0]
    # the shared deterministic traffic source: one seeded request pool,
    # batch b serves its first b requests (same bytes as the zoo/pipeline
    # load generators draw)
    pool = seeded_payloads(max(batches), (k0,), seed=0)
    xs = {b: jnp.asarray(np.stack(pool[:b])) for b in batches}

    # consistency: batching amortizes traffic, never changes math — the
    # batched head forward must be bitwise equal to the per-sample
    # forwards unbatched serving would run (rows are independent in the
    # batch-tiled SA-FC kernel).  Row independence is batch-agnostic, so
    # the check is capped: b=256 would add hundreds of interpret-mode
    # single-sample forwards to the nightly tier for no extra assurance.
    bchk = max(b for b in batches if b <= 16)
    batched = np.asarray(cnn.fc_head_forward(head, params, xs[bchk],
                                             eng=eng))
    singles = np.concatenate(
        [np.asarray(cnn.fc_head_forward(head, params, xs[bchk][i:i + 1],
                                        eng=eng))
         for i in range(bchk)])
    check = {"name": f"parity/{net}_w{width_mult:.3g}_b{bchk}"
                     "/batched_bitwise_equal_singles",
             "passed": bool(np.array_equal(batched, singles)),
             "detail": f"max|diff|="
                       f"{float(np.max(np.abs(batched - singles)))}"}

    fns = {"b1": lambda: cnn.fc_head_forward(head, params, xs[1][:1],
                                             eng=eng)}
    for b in batches:
        if b == 1:
            continue
        fns[f"b{b}"] = (lambda b=b: cnn.fc_head_forward(head, params,
                                                        xs[b], eng=eng))
    med = interleaved_medians(fns, reps=reps, trials=trials)
    rows = []
    for b in batches:
        batched = med[f"b{b}"] / b
        single = med["b1"]
        rows.append({"b": b,
                     "batched_us_per_sample": round(batched * 1e6, 1),
                     "unbatched_us_per_sample": round(single * 1e6, 1),
                     "amortization": round(single / batched, 2)})
    return {"net": net, "width_mult": width_mult,
            "head": [[k, n, act] for k, n, act in head],
            "reps": reps, "trials": trials, "rows": rows,
            "checks": [check]}


def emit(out_path: str = "BENCH_fc_batch.json", *,
         tier: str = "fast") -> list[Row]:
    """Run the benchmark, write the JSON artifact, return CSV rows for
    benchmarks/run.py."""
    planner = planner_section()
    walls = [wall_section(net, wm, batches, reps=reps, trials=trials)
             for net, wm, batches, reps, trials in WALL_CONFIGS[tier]]
    pb = planner["per_batch"]
    headline = {
        "stack_weight_MiB_per_sample_b1":
            round(pb["1"]["stack_weight_bytes_per_sample"] / 2**20, 2),
        "stack_weight_MiB_per_sample_b64":
            round(pb["64"]["stack_weight_bytes_per_sample"] / 2**20, 2),
        "planner_amortization_b64_vs_b1":
            planner["stack_amortization_b64_vs_b1"],
        "flip_batch": planner["flip_batch"],
        "wall_amortization_at_bmax":
            max(r["amortization"] for w in walls for r in w["rows"]),
    }
    checks = [c for w in walls for c in w["checks"]]
    # planner invariants: weights-bytes/sample must be non-increasing in
    # the batch, and the b=64-vs-b=1 amortization must clear the 32x bar
    curve = [pb[str(b)]["stack_weight_bytes_per_sample"]
             for b in planner["batches"]]
    checks.append({"name": "planner/weights_per_sample_non_increasing",
                   "passed": all(a >= b for a, b in zip(curve, curve[1:])),
                   "detail": f"curve={curve}"})
    checks.append({"name": "planner/amortization_b64_vs_b1_ge_32",
                   "passed": bool(
                       headline["planner_amortization_b64_vs_b1"] >= 32),
                   "detail": f"{headline['planner_amortization_b64_vs_b1']}"
                             "x"})
    results = {"bench": "fc_batch", "tier": tier,
               "backend": "pallas-interpret-cpu",
               "planner": planner, "wall": walls, "headline": headline,
               "checks": checks}
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(results, fh, indent=2)

    rows: list[Row] = []
    for b in planner["batches"]:
        e = pb[str(b)]
        rows.append((f"fc_batch/planner/alexnet_head_b{b}", 0.0,
                     f"{e['stack_weight_bytes_per_sample'] / 2**20:.2f} MiB "
                     f"weights/sample (x"
                     f"{pb['1']['stack_weight_bytes_per_sample'] / max(1, e['stack_weight_bytes_per_sample']):.0f}"
                     f" amortized vs b=1)"))
    for w in walls:
        for r in w["rows"]:
            rows.append((
                f"fc_batch/wall/{w['net']}_w{w['width_mult']:.3g}_b{r['b']}",
                r["batched_us_per_sample"],
                f"per-sample, vs {r['unbatched_us_per_sample']:.1f}us "
                f"unbatched ({r['amortization']:.2f}x)"))
    rows.append(("fc_batch/json", 0.0,
                 f"wrote {out_path} (planner amortization b64 "
                 f"{headline['planner_amortization_b64_vs_b1']:.0f}x, "
                 f"flip fc1 @ b={planner['flip_batch']['fc1']})"))
    raise_on_failed_checks(checks)
    return rows


def bench_rows() -> list[Row]:
    """run.py group entry: fast tier, writes BENCH_fc_batch.json."""
    return emit("BENCH_fc_batch.json", tier="fast")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_fc_batch.json")
    tier = ap.add_mutually_exclusive_group()
    tier.add_argument("--fast", dest="tier", action="store_const",
                      const="fast", default="fast",
                      help="CI smoke: width-scaled head wall (seconds)")
    tier.add_argument("--full", dest="tier", action="store_const",
                      const="full",
                      help="nightly: quarter- and full-width heads up to "
                           "b=256")
    args = ap.parse_args()
    run_emit_cli(emit, args.out, args.tier)


if __name__ == "__main__":
    main()
