"""Benchmark harness entry point: one benchmark per paper table/figure plus
kernel/planner micro-benches and the dry-run roofline report.

Runs every registered benchmark group and prints ``name,us_per_call,
derived`` CSV rows (scaffold contract).  The artifact-writing groups
(conv_fused, fc_batch, pipeline_serve, zoo_serve, chaos_serve,
fleet_serve) also write their committed ``BENCH_*.json`` files at the
fast tier — see docs/benchmarks.md for what each artifact pins and how
``check_bench.py`` gates it.

    PYTHONPATH=src python benchmarks/run.py            # everything
    PYTHONPATH=src python benchmarks/run.py --list     # group names
    PYTHONPATH=src python benchmarks/run.py --only fleet_serve
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

# script execution puts benchmarks/ (not the repo root) on sys.path;
# the repo root is what makes `from benchmarks import ...` resolve
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _groups() -> list[tuple[str, object]]:
    from benchmarks import chaos_serve, conv_fused, fc_batch, \
        fleet_serve, kernel_bench, paper_figures, pipeline_serve, \
        roofline_report, zoo_serve

    groups: list[tuple[str, object]] = []
    groups += [("paper_figures", fn) for fn in paper_figures.ALL]
    groups += [("kernel_bench", fn) for fn in kernel_bench.ALL]
    groups += [("roofline_report", fn) for fn in roofline_report.ALL]
    # fused SA-CONV->maxpool epilogue: wall + planner bytes, fused vs
    # unfused — also writes the machine-readable BENCH_conv_fused.json
    groups += [("conv_fused", conv_fused.bench_rows)]
    # batch-amortized SA-FC: weights-bytes/sample amortization curve +
    # interleaved-median wall — writes BENCH_fc_batch.json
    groups += [("fc_batch", fc_batch.bench_rows)]
    # dual-array pipelined serving: modeled makespan ratios + crossover
    # batches + pipelined-vs-sequential wall — writes BENCH_pipeline.json
    groups += [("pipeline_serve", pipeline_serve.bench_rows)]
    # multi-tenant model-zoo serving: seeded Poisson trace under
    # fifo/smf/edf with per-tenant SLO accounting — writes BENCH_zoo.json
    groups += [("zoo_serve", zoo_serve.bench_rows)]
    # fault-injected zoo serving: seeded wave-level chaos vs admission
    # control / retry / int8 degraded mode — writes BENCH_chaos.json
    groups += [("chaos_serve", chaos_serve.bench_rows)]
    # sharded serving fleet: N data-parallel replicas, replica-granular
    # chaos (kill/partition/stall), drain-to-peer + elastic replan,
    # cooperative sharded waves — writes BENCH_sharded.json
    groups += [("fleet_serve", fleet_serve.bench_rows)]
    return groups


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks/run.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--list", action="store_true",
                    help="print the group names and exit")
    ap.add_argument("--only", action="append", default=None,
                    metavar="GROUP",
                    help="run only this group (repeatable; see --list)")
    args = ap.parse_args(argv)

    groups = _groups()
    names = sorted({name for name, _ in groups})
    if args.list:
        print("\n".join(names))
        return
    if args.only:
        unknown = sorted(set(args.only) - set(names))
        if unknown:
            ap.error(f"unknown group(s) {unknown}; known: {names}")
        groups = [(n, fn) for n, fn in groups if n in args.only]

    print("name,us_per_call,derived")
    failures = 0
    for _, fn in groups:
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:                      # noqa: BLE001
            failures += 1
            print(f"{fn.__module__}.{fn.__name__},0,ERROR {e!r}",
                  file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} benchmark groups failed")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
