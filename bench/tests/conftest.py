import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(workload: str, tmp_path: Path, **mix):
    """``workload`` at a size the CPU interpreter runs in seconds: width
    0.125 at 67x67, a pool of 6 images and a lighter mix."""
    import dataclasses
    import json

    from bench import spec

    cell = spec.load(ROOT / "BENCHMARK.json", workload)
    m = json.loads(cell.traffic.read_text())
    m.update(pool=6, **mix)
    path = tmp_path / cell.traffic.name
    path.write_text(json.dumps(m))
    return dataclasses.replace(
        cell, traffic=path,
        config=dict(cell.config, in_res=67, width_mult=0.125))


def cpu_peaks() -> dict:
    import json

    from bench import spec
    peaks = json.loads((spec.BENCH / "peaks.json").read_text())
    return {"cpu": peaks["TPU v5 lite"]}
