"""The serving path's own spans and counters (``repro.serve.telemetry``):
the ring and its refusals, the switch, the compile counter, the
profiler annotations, and the span tree one ``ModelZooServer.serve()``
and one cooperative fleet wave leave behind."""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import cnn_server, telemetry
from repro.serve.faults import ChaosConfig, FaultInjector
from repro.serve.zoo import ModelZooServer, ZooRequest, build_zoo

ROOT = Path(__file__).resolve().parents[1]
RES, WIDTH = 67, 0.125


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((RES, RES, 3)).astype(np.float32)
            for _ in range(n)]


def _zoo_models():
    return build_zoo(["alexnet"], seed=0, in_res={"alexnet": RES},
                     width_mult=WIDTH, max_batch=2)


@pytest.fixture(scope="module")
def zoo_models():
    return _zoo_models()


# -- the recorder --------------------------------------------------------------

def test_span_nesting_parent_links_and_self_time():
    rec = telemetry.Recorder(capacity=64)
    with rec.span("outer", 3):
        time.sleep(0.003)
        with rec.span("inner", 3):
            time.sleep(0.002)
            with rec.span("leaf", 3):
                pass
        with rec.span("inner", 4):
            pass
    out = rec.records()
    assert [r.name for r in out] == ["outer", "inner", "leaf", "inner"]
    outer, inner, leaf, inner2 = out
    assert outer.parent == -1
    assert inner.parent == inner2.parent == outer.seq
    assert leaf.parent == inner.seq
    assert [r.ident for r in out] == [3, 3, 3, 4]
    for child, parent in ((inner, outer), (leaf, inner), (inner2, outer)):
        assert parent.start_ns <= child.start_ns <= child.end_ns \
            <= parent.end_ns
    # self time: the span's duration less what its children cover
    kids = [r for r in out if r.parent == outer.seq]
    self_ns = (outer.end_ns - outer.start_ns) \
        - sum(r.end_ns - r.start_ns for r in kids)
    assert self_ns >= 3_000_000                 # the sleep outside inner
    assert inner.end_ns - inner.start_ns >= 2_000_000


def test_span_closes_when_its_body_raises():
    rec = telemetry.Recorder(capacity=8)
    with pytest.raises(ValueError):
        with rec.span("fails"):
            raise ValueError("boom")
    with rec.span("after"):
        pass
    out = rec.records()
    assert [r.name for r in out] == ["fails", "after"]
    assert out[1].parent == -1                  # the stack was unwound


def test_ring_returns_none_once_wrapped_inside_the_window():
    rec = telemetry.Recorder(capacity=8)
    lo = time.perf_counter_ns()
    for _ in range(6):
        with rec.span("a"):
            pass
    assert len(rec.records(lo)) == 6
    mid = time.perf_counter_ns()
    for _ in range(6):
        with rec.span("b"):
            pass
    hi = time.perf_counter_ns()
    assert rec.records(lo, hi) is None          # four "a" were overwritten
    after = rec.records(mid, hi)                # nothing lost after mid
    assert [r.name for r in after] == ["b"] * 6
    assert len(rec.records(mid)) == 6
    # the ring never grows
    assert len(rec._start) == len(rec._end) == rec.capacity == 8


def test_ring_refuses_a_window_whose_open_span_was_overwritten():
    rec = telemetry.Recorder(capacity=4)
    lo = time.perf_counter_ns()
    with rec.span("long"):
        for _ in range(5):
            with rec.span("short"):
                pass
    assert rec.records(lo) is None
    assert rec.records(time.perf_counter_ns()) == []


def test_window_bounds_drop_spans_outside():
    rec = telemetry.Recorder(capacity=16)
    with rec.span("before"):
        pass
    lo = time.perf_counter_ns()
    with rec.span("inside"):
        pass
    hi = time.perf_counter_ns()
    with rec.span("after"):
        pass
    assert [r.name for r in rec.records(lo, hi)] == ["inside"]


def test_capacity_must_be_a_power_of_two():
    with pytest.raises(ValueError):
        telemetry.Recorder(capacity=12)


def test_disable_records_nothing_and_enable_resumes():
    rec = telemetry.Recorder(capacity=16)
    rec.disable()
    assert not rec.enabled
    s1, s2 = rec.span("x"), rec.span("y", 5)
    assert s1 is s2                              # one shared no-op context
    with s1:
        rec.count("n")
    assert rec.records() == [] and rec.counts() == {}
    rec.enable()
    with rec.span("x"):
        rec.count("n", 2)
    assert [r.name for r in rec.records()] == ["x"]
    assert rec.counts() == {("n", "x"): 2}


def test_counts_key_on_the_innermost_open_span():
    rec = telemetry.Recorder(capacity=16)
    rec.count("rows")
    with rec.span("outer"):
        rec.count("rows", 3)
        with rec.span("inner"):
            rec.count("rows", 2)
        rec.count("rows")
    assert rec.counts() == {("rows", None): 1, ("rows", "outer"): 4,
                            ("rows", "inner"): 2}


def test_forced_recompile_is_counted_on_its_span():
    seen = []

    def listener(event, duration, **_):
        if event == telemetry.COMPILE_EVENT:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        before = telemetry.counts()
        with telemetry.span("test.recompile"):
            f = jax.jit(lambda x: x * 3.0 + 1.0)
            f(jnp.ones((3, 5))).block_until_ready()
            f(jnp.ones((7, 11))).block_until_ready()   # a new shape
        after = telemetry.counts()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    key = ("compile", "test.recompile")
    got = after.get(key, 0) - before.get(key, 0)
    assert got >= 2
    assert got == len(seen)


def test_module_switch_turns_the_shared_recorder_off_and_on():
    telemetry.disable()
    try:
        lo = time.perf_counter_ns()
        with telemetry.span("test.off"):
            telemetry.count("test.off")
        assert telemetry.records(lo) == []
        assert ("test.off", None) not in telemetry.counts()
    finally:
        telemetry.enable()
    with telemetry.span("test.on"):
        pass
    assert [r.name for r in telemetry.records(lo)] == ["test.on"]


def test_spans_are_profiler_annotations_of_the_same_name(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    lo = time.perf_counter_ns()
    try:
        with telemetry.span("test.prof.outer"):
            time.sleep(0.004)
            with telemetry.span("test.prof.inner"):
                time.sleep(0.002)
        hi = time.perf_counter_ns()
    finally:
        jax.profiler.stop_trace()
    mine = {r.name: r.end_ns - r.start_ns for r in telemetry.records(lo, hi)}
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    traced = {e.name: e.duration_ns
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("test.prof.")}
    assert set(traced) == set(mine) == {"test.prof.outer", "test.prof.inner"}
    for name, ns in mine.items():
        assert abs(traced[name] - ns) <= max(0.05 * ns, 50_000), name


# -- the wave executor ---------------------------------------------------------

def test_waves_are_bounded_and_trace_joins_the_stages(monkeypatch):
    from repro.models import cnn

    monkeypatch.setattr(cnn_server, "RECENT_WAVES", 2)
    params = cnn.init_cnn("alexnet", jax.random.PRNGKey(0), in_res=RES,
                          width_mult=WIDTH)
    srv = cnn_server.CNNServer("alexnet", params, in_res=RES,
                               width_mult=WIDTH, max_batch=1)
    for i, im in enumerate(_images(4)):
        srv.submit(cnn_server.CNNRequest(uid=i, image=im))
    done = srv.run(pipelined=False)
    assert len(done) == 4
    assert [w.wave for w in srv.waves] == [2, 3]       # the latest two
    w = srv.waves[-1]
    assert [r.stage for r in w.trace] == \
        ["conv"] * len(w.conv_trace) + ["fc"] * len(w.fc_trace)
    assert w.trace is not w.trace                       # built when read
    assert w.schedule_hits == sum(r.schedule == "hit" for r in w.trace)


# -- the zoo -------------------------------------------------------------------

def test_zoo_serve_span_tree(zoo_models):
    """Two waves in one serve(): the exact tree, with the call index on
    the zoo's spans and the wave index on the executor's."""
    zoo = ModelZooServer(zoo_models)
    rows0 = telemetry.counts().get(("cnn.rows", "cnn.wave"), 0)
    for i, im in enumerate(_images(4)):
        zoo.submit(ZooRequest(uid=i, model="alexnet", image=im))
    lo = time.perf_counter_ns()
    rep = zoo.serve()
    hi = time.perf_counter_ns()
    assert len(rep.decisions) == 2 and len(rep.served) == 4
    out = telemetry.records(lo, hi)
    by_seq = {r.seq: r for r in out}

    def tree(parent):
        return [(r.name, r.ident, tree(r.seq)) for r in out
                if r.parent == parent]

    first = zoo_models[0].server._wave_counter - 2
    wave = [("cnn.upload", None, []), ("cnn.conv_dispatch", None, []),
            ("cnn.fc_dispatch", None, []), ("cnn.logits_wait", None, [])]

    def waves(w):
        return [(n, w, k) for n, _, k in wave]

    roots = [r for r in out if r.parent not in by_seq]
    assert [r.name for r in roots] == ["zoo.serve"]
    call = roots[0].ident
    assert tree(roots[0].seq) == [
        ("zoo.schedule", call, []),
        ("zoo.execute", call, [
            ("cnn.wave", first, waves(first)),
            ("zoo.guard", call, []),
            ("cnn.wave", first + 1, waves(first + 1)),
            ("zoo.guard", call, [])]),
        ("zoo.account", call, [])]
    assert telemetry.counts()[("cnn.rows", "cnn.wave")] - rows0 == 4

    for i, im in enumerate(_images(1, seed=1), start=10):
        zoo.submit(ZooRequest(uid=i, model="alexnet", image=im))
    lo = time.perf_counter_ns()
    zoo.serve()
    again = telemetry.records(lo, time.perf_counter_ns())
    assert {r.ident for r in again if r.name.startswith("zoo.")} == \
        {call + 1}


def test_guard_rejects_are_counted_on_the_guard():
    zoo = ModelZooServer(_zoo_models(), faults=FaultInjector(
        ChaosConfig(seed=0, corrupt_rate=1.0)))
    key = ("zoo.guard_rejects", "zoo.guard")
    before = telemetry.counts().get(key, 0)
    for i, im in enumerate(_images(2)):
        zoo.submit(ZooRequest(uid=i, model="alexnet", image=im))
    rep = zoo.serve()
    refused = sum(len(e.uids) for e in rep.events if e.kind == "corrupt")
    assert refused > 0
    assert telemetry.counts().get(key, 0) - before == refused


# -- the fleet -----------------------------------------------------------------

def test_two_replica_fleet_spans_and_guard_rows(zoo_models):
    """A fleet is the same plane: its drain is ``zoo.schedule`` and one
    ``zoo.guard`` per executed wave, and ``zoo.guard_rows`` counts every
    row it delivered."""
    from repro.serve.fleet import FleetServer

    fleet = FleetServer(zoo_models, n_replicas=2)
    key = ("zoo.guard_rows", "zoo.guard")
    before = telemetry.counts().get(key, 0)
    for i, im in enumerate(_images(4)):
        fleet.submit(ZooRequest(uid=i, model="alexnet", image=im))
    lo = time.perf_counter_ns()
    rep = fleet.serve()
    hi = time.perf_counter_ns()
    assert len(rep.served) == 4
    assert {d.replica for d in rep.decisions} == {"r0", "r1"}
    names = [r.name for r in telemetry.records(lo, hi)]
    assert names.count("zoo.schedule") == 1
    assert names.count("zoo.guard") == len(rep.decisions)
    assert telemetry.counts().get(key, 0) - before == 4


FLEET = r"""
import json, sys, time
import numpy as np
import jax
from repro.serve import telemetry
from repro.serve.fleet import FleetServer
from repro.serve.zoo import FIFOPolicy, ZooRequest, build_zoo

assert len(jax.devices()) == 4
models = build_zoo(["alexnet-int8"], seed=0, in_res={"alexnet": 67},
                   width_mult=0.125, max_batch=2)
fleet = FleetServer(models, n_replicas=4, policy=FIFOPolicy(),
                    shard_waves=True)
inside = []            # compiles jax.monitoring reports inside the wave
state = {"on": False}

def listener(event, duration, **_):
    if state["on"] and event == telemetry.COMPILE_EVENT:
        inside.append(event)

jax.monitoring.register_event_duration_secs_listener(listener)
run = fleet._execute_sharded

def watched(*a, **kw):
    state["on"] = True
    try:
        return run(*a, **kw)
    finally:
        state["on"] = False

fleet._execute_sharded = watched
rng = np.random.default_rng(0)
for k in range(6):
    fleet.submit(ZooRequest(uid=k, model="alexnet-int8", arrival_s=0.0,
                            image=rng.standard_normal((67, 67, 3))
                            .astype(np.float32)))
before = telemetry.counts()
lo = time.perf_counter_ns()
rep = fleet.serve()
hi = time.perf_counter_ns()
after = telemetry.counts()
key = ("compile", "fleet.sharded_wave")
print(json.dumps({
    "served": len(rep.served),
    "sharded": sum(d.sharded for d in rep.decisions),
    "spans": [[r.name, r.parent, r.ident, r.seq]
              for r in telemetry.records(lo, hi)],
    "compiles": after.get(key, 0) - before.get(key, 0),
    "seen": len(inside)}))
"""


def test_fleet_cooperative_wave_compiles_land_on_its_span():
    """On four virtual CPU devices: the fleet's spans, and every compile
    of a cooperative ``data=4`` wave counted on ``fleet.sharded_wave``,
    as many as ``jax.monitoring`` reported while the wave ran."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", FLEET], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["served"] == 6 and r["sharded"] >= 1
    spans = r["spans"]
    (root,) = [s for s in spans if s[0] == "zoo.execute"]
    waves = [s for s in spans if s[0] == "fleet.sharded_wave"]
    assert len(waves) == r["sharded"]
    assert all(w[1] == root[3] and w[2] == root[2] for w in waves)
    assert r["compiles"] > 0
    assert r["compiles"] == r["seen"]
