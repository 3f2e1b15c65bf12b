"""The control: the reference at the next precision below the stated one,
judged by the run's own verdict, must come out not correct.  On the chip
``bench/control.py`` reads it at the cells' size; here it runs at a small
size on the CPU, where ``Precision.HIGH`` is computed exactly, so only the
int8 configuration's int4 control can show its failure."""
import json

import pytest

from bench import control, spec


@pytest.mark.parametrize("name", ["alexnet", "alexnet-int8"])
def test_control_readings(name):
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    limit = float(cfg["correct"]["max_rel_err"])
    cfg.update(in_res=67, width_mult=0.125)
    r = control.readings(cfg, 2 ** 31 + 23, 4)
    assert set(r) == ({"high", "int4"} if name == "alexnet-int8"
                      else {"high"})
    for correct, checks in r.values():
        assert checks["max_rel_err"]["limit"] == limit
        assert checks["unserved"]["value"] == 0
    assert 0.0 <= r["high"][1]["max_rel_err"]["value"] < 1.0
    if name == "alexnet-int8":
        correct, checks = r["int4"]
        assert correct is False
        assert checks["max_rel_err"]["value"] > 100 * limit
