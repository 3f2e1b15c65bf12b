"""Run one cell of the chip benchmark once and print its result line.

    python3 bench/run.py --workload alexnet.bulk --seed 7 --seconds 10 --trace 0

The cell, its configuration, its traffic mix and its metrics are looked up
by name in ``BENCHMARK.json`` beside this directory.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
Diagnostics and the same checks go to standard error.

Exits non-zero and prints no result when no TPU is attached, when fewer
chips are attached than the cell asks for, when the device kind has no
entry in ``bench/peaks.json``, or when the program is not beside this
directory.
"""
import time

T0 = time.perf_counter()   # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import cell, spec
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: the program is not beside bench/ ({e})",
              file=sys.stderr)
        return 2
    try:
        result = cell.run(spec.load(ROOT / "BENCHMARK.json", args.workload),
                          seed=args.seed, seconds=args.seconds,
                          traced=bool(args.trace), t0=T0)
    except cell.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
