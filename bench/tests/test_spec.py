"""``BENCHMARK.json`` and the files it names are found by name, and a cell
added as files alone is found the same way."""
import json
import re

import numpy as np
import pytest

from bench import spec, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((spec.ROOT / "BENCHMARK.json").read_text())


def test_every_cell_resolves(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = spec.load(spec.ROOT / "BENCHMARK.json", w["name"])
        assert cell.config["name"] == w["config"]
        traffic.load(cell.traffic)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names & e2e
            assert callable(spec.reader(m["name"]))


def test_names_units_and_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in bench["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] == []
        assert float(cfg["correct"]["max_rel_err"]) > 0
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)


def test_a_cell_added_as_files_is_found_by_name(tmp_path):
    """A later PR adds a configuration, a mix and a metric as new files and
    one entry each; the harness finds them with no code changed."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    cfg = json.loads((spec.BENCH / "configs" / "alexnet.json").read_text())
    cfg["name"] = "alexnet-b64"
    cfg["admission_cap"] = 64
    (tmp_path / "bench" / "configs" / "alexnet-b64.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "trickle.json").write_text(
        json.dumps({"loop": "open", "rate_per_s": 30, "pool": 16}))
    (tmp_path / "bench" / "metrics" / "queue_wait_ms.open.py").write_text(
        "def read(run):\n    return None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "alexnet-b64",
                     "file": "bench/configs/alexnet-b64.json"}],
        "workloads": [{"name": "alexnet-b64.trickle",
                       "config": "alexnet-b64", "traffic": "trickle",
                       "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "images_per_s", "unit": "images/s",
                        "workloads": ["someone.else"]}],
        "per_layer": [{"name": "queue_wait_ms.open", "unit": "ms",
                       "moves": "setup_s"}]}))
    cell = spec.load(tmp_path / "BENCHMARK.json", "alexnet-b64.trickle")
    assert cell.config["admission_cap"] == 64
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    mix = traffic.load(cell.traffic)
    assert (mix.loop, mix.rate_per_s, mix.clump) == ("open", 30.0, 1)
    read = spec.reader("queue_wait_ms.open", tmp_path / "bench" / "metrics")
    assert read(None) is None


def test_open_loop_offers_the_same_load_for_every_seed():
    mix = traffic.Mix(name="t", loop="open", pool=8, rate_per_s=50.0,
                      clump=3)
    a = traffic.due_times(mix, 4.0, 1)
    b = traffic.due_times(mix, 4.0, 2 ** 31 + 7)
    assert len(a) == len(b) == 3 * 200
    assert a[0] == b[0] == 0.0 and a.max() < 4.0
    assert sorted(set(a.round(12))) != sorted(set(b.round(12)))
    def gaps(due):          # the clumps' gaps, the window's end closing them
        return sorted(np.diff(np.append(due[::3], 4.0)).round(9))
    assert gaps(a) == gaps(b)
    assert (traffic.due_times(mix, 4.0, 1) == a).all()
