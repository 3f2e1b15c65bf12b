"""Runs of a cell with the timed path broken underneath must come out not
correct.  Each drives the whole run except the look for a chip, at a size
the CPU interpreter runs: once sound, then once per fault the cell can
have."""
import json
import os
import subprocess
import sys
import time

import pytest

from conftest import ROOT, cpu_peaks, tiny_cell


def _run(cell, traced=False):
    from bench import cell as cellmod
    return cellmod.run(cell, seed=2 ** 31 + 17, seconds=1.0, traced=traced,
                       t0=time.perf_counter(), require_tpu=False,
                       peaks=cpu_peaks())


def _altered(monkeypatch):
    """An answer altered where it is produced: the FC stage's first row
    gains a thousandth of the largest logit on one class."""
    import jax.numpy as jnp

    from repro.models import cnn
    orig = cnn.cnn_fc_stage

    def broken(*a, **kw):
        out = orig(*a, **kw)
        return out.at[0, 0].add(1e-3 * jnp.max(jnp.abs(out)))
    monkeypatch.setattr(cnn, "cnn_fc_stage", broken)


def _half(monkeypatch):
    """Half of each wave left out: the executor hands back only the first
    half of the rows it was given."""
    from repro.serve.cnn_server import CNNServer
    orig = CNNServer.step_wave

    def broken(self):
        done = orig(self)
        return done[:len(done) // 2]
    monkeypatch.setattr(CNNServer, "step_wave", broken)


@pytest.mark.parametrize("workload", ["alexnet.bulk", "alexnet-int8.bulk",
                                      "alexnet.poisson"])
@pytest.mark.parametrize("fault", ["sound", "altered", "half"])
def test_broken_path_is_not_correct(workload, fault, tmp_path, monkeypatch):
    mix = {"chunk": 16} if "bulk" in workload else {"rate_per_s": 6}
    cell = tiny_cell(workload, tmp_path, **mix)
    if fault == "altered":
        _altered(monkeypatch)
    elif fault == "half":
        _half(monkeypatch)
    r = _run(cell, traced=fault == "sound" and workload == "alexnet.poisson")
    assert r["attempted"] > 0
    assert r["correct"] is (fault == "sound"), r["checks"]
    if fault == "altered":
        assert r["checks"]["max_rel_err"]["value"] > 1e-4
        assert r["failed"] == 0
    if fault == "half":
        assert r["failed"] > 0


FLEET = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
from pathlib import Path
import jax, jax.numpy as jnp
from conftest import tiny_cell, cpu_peaks
from bench import cell as cellmod
from repro.models import cnn
if {broken!r}:
    orig = cnn.cnn_forward
    def broken(*a, **kw):
        out = orig(*a, **kw)
        # the exchange left out: only chip 0's rows come back
        return jnp.where(jax.lax.axis_index("data") == 0, out, 0.0)
    cnn.cnn_forward = broken
cell = tiny_cell("alexnet.poisson", Path({tmp!r}), rate_per_s=2, clump=24,
                 server={{"kind": "fleet", "replicas": 4,
                          "shard_waves": True}})
r = cellmod.run(cell, seed=2 ** 31 + 19, seconds=1.0, traced=False,
                t0=time.perf_counter(), require_tpu=False, peaks=cpu_peaks())
print(json.dumps(r))
"""


@pytest.mark.parametrize("broken", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_fleet_without_the_exchange_is_not_correct(broken, tmp_path):
    """The harness's four-replica fleet path (bursts of 24, cooperative
    ``data=4`` waves) on four CPU devices, in a child process that asks
    for them before JAX starts."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FLEET.format(root=str(ROOT), src=str(ROOT / "src"),
                        tests=str(ROOT / "bench" / "tests"),
                        broken=broken, tmp=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"] is (not broken), r["checks"]
    if broken:
        assert r["checks"]["max_rel_err"]["value"] > 0.1
