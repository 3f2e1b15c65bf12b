"""The wave executor's two stages as compiled programs.

Each stage of each wave shape (the conv stack, the classifier head) is
traced once through the engine into one ``jax.jit`` program, with the
parameters as its arguments.  After warm-up a wave is one program call
per stage: nothing is traced or compiled again, the dispatch records the
trace made are replayed into every wave's report, and the band counters
grow per wave as they did when each layer was dispatched on its own.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import DispatchPolicy, Engine
from repro.models import cnn
from repro.serve import telemetry
from repro.serve.zoo import ModelZooServer, ZooRequest, build_zoo

RES = {"alexnet": 67, "vgg16": 32}
WIDTH = 0.125
#: a budget under which VGG-16's stem at 32x32 runs in row bands
BANDED_BUDGET = 3_000_000


class _Serve:
    """One model in a zoo of its own: serve batches of fresh requests."""

    def __init__(self, model) -> None:
        self.model, self.zoo = model, ModelZooServer([model])
        self.res = model.server.in_res
        self.uids = itertools.count()

    def __call__(self, n: int, seed: int) -> list[ZooRequest]:
        rng = np.random.default_rng(seed)
        reqs = []
        for _ in range(n):
            reqs.append(ZooRequest(
                uid=next(self.uids), model=self.model.name,
                image=rng.standard_normal((self.res, self.res, 3))
                .astype(np.float32)))
            self.zoo.submit(reqs[-1])
        self.zoo.serve()
        assert [r.status for r in reqs] == ["served"] * n
        return reqs


@pytest.fixture(scope="module")
def alexnets():
    """fp32 and int8 AlexNet at 67x67, width 0.125, waves of up to 4."""
    models = build_zoo(("alexnet", "alexnet-int8"), seed=0, in_res=RES,
                       width_mult=WIDTH, max_batch=4)
    return {m.name: _Serve(m) for m in models}


@pytest.fixture(scope="module")
def banded_vgg():
    eng = Engine(backend="pallas",
                 policy=DispatchPolicy(vmem_budget=BANDED_BUDGET))
    model, = build_zoo(("vgg16",), seed=3, in_res=RES, width_mult=WIDTH,
                       max_batch=4, engine=eng)
    return _Serve(model)


def _growth(before: dict, name: str) -> dict:
    """How much each ``name`` counter grew since ``before``, by span."""
    out: dict = {}
    for (n, span), v in telemetry.counts().items():
        if n == name and v != before.get((n, span), 0):
            out[span] = v - before.get((n, span), 0)
    return out


@pytest.mark.parametrize("name", ["alexnet", "alexnet-int8"])
def test_warm_serve_builds_nothing_and_calls_each_stage_once_a_wave(
        alexnets, name):
    """After a warm-up ``serve()``, serving the same wave shapes again
    traces no program and compiles nothing; each wave makes one call of
    each stage's program, counted under the span that dispatches it."""
    serve = alexnets[name]
    model = serve.model
    serve(5, seed=0)                                  # waves of 4 and 1
    before, waves = telemetry.counts(), len(model.server.waves)
    serve(5, seed=1)
    new = len(model.server.waves) - waves
    assert new == 2
    assert _growth(before, "cnn.stage_builds") == {}
    assert _growth(before, "compile") == {}
    assert _growth(before, "cnn.stage_calls") == {
        "cnn.conv_dispatch": new, "cnn.fc_dispatch": new}


def test_stage_functions_wrapped_after_warm_up_compile_nothing(
        alexnets, monkeypatch):
    """A benchmark wraps the module's stage functions in its own spans
    after warm-up; the programs do not key on those attributes, so the
    wrapped calls still trace and compile nothing."""
    serve = alexnets["alexnet"]
    serve(4, seed=2)
    calls = []

    def wrap(fn):
        @functools.wraps(fn)
        def wrapped(*a, **kw):
            calls.append(fn.__name__)
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(cnn, "cnn_conv_stage", wrap(cnn.cnn_conv_stage))
    monkeypatch.setattr(cnn, "cnn_fc_stage", wrap(cnn.cnn_fc_stage))
    before = telemetry.counts()
    serve(4, seed=3)
    assert calls == ["cnn_conv_stage", "cnn_fc_stage"]
    assert _growth(before, "cnn.stage_builds") == {}
    assert _growth(before, "compile") == {}


def test_wave_reports_and_band_counters_count_every_wave(banded_vgg):
    """Replayed records keep the per-wave accounting: every wave's report
    lists conv1..conv13 and fc1..fc3, each a schedule hit tagged with its
    stage and wave, and the band counters grow by each wave's plans —
    VGG-16 with its stem forced into row bands."""
    serve = banded_vgg
    model = serve.model
    serve(3, seed=4)
    before, waves = telemetry.counts(), len(model.server.waves)
    serve(3, seed=5)
    serve(3, seed=6)
    reports = model.server.waves[waves:]
    assert len(reports) == 2
    convs = [f"conv{i}" for i in range(1, 14)]
    for rep in reports:
        assert [r.name for r in rep.conv_trace] == convs
        assert [r.name for r in rep.fc_trace] == ["fc1", "fc2", "fc3"]
        assert rep.schedule_hits == 16
        assert all(r.schedule == "hit" and r.wave == rep.wave
                   for r in rep.trace)
        assert {r.stage for r in rep.conv_trace} == {"conv"}
        assert {r.stage for r in rep.fc_trace} == {"fc"}
    assert reports[0].wave != reports[1].wave
    plans = [r.conv_plan for r in reports[0].conv_trace]
    assert plans[0].bands > 1                         # the stem is banded
    assert _growth(before, "sa_conv.bands") == {
        "cnn.conv_dispatch": 2 * sum(p.bands for p in plans)}
    halo = _growth(before, "sa_conv.halo_bytes")["cnn.conv_dispatch"]
    slab = _growth(before, "sa_conv.slab_bytes")["cnn.conv_dispatch"]
    assert halo > 0 and halo % 2 == 0 and slab % 2 == 0
    assert _growth(before, "cnn.stage_builds") == {}


@pytest.mark.parametrize("name", ["alexnet", "alexnet-int8"])
def test_served_logits_bitwise_equal_batch_one_forward(alexnets, name):
    """A served row is bitwise the row the batch-1 forward gives, through
    the batch-1 programs, fp32 and int8."""
    model = alexnets[name].model
    reqs = alexnets[name](3, seed=7)
    eng = Engine(backend="pallas")
    for r in reqs:
        one = cnn.cnn_forward(model.spec.net, model.params,
                              jnp.asarray(r.image)[None], eng=eng)
        np.testing.assert_array_equal(np.asarray(one)[0], r.logits)


def test_program_takes_the_parameters_as_arguments(alexnets):
    """Two parameter sets of one shape run through one program: the
    second traces nothing and computes with its own weights."""
    model = alexnets["alexnet"].model
    eng = Engine(backend="pallas")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 67, 67, 3))
    other = jax.tree.map(lambda a: a * 0.5, model.params)
    first = cnn.cnn_conv_stage("alexnet", model.params, x, eng=eng)
    before = telemetry.counts()
    with eng.tracing() as tr:
        second = cnn.cnn_conv_stage("alexnet", other, x, eng=eng)
    assert _growth(before, "cnn.stage_builds") == {}
    assert _growth(before, "cnn.stage_calls") == {None: 1}
    assert [r.name for r in tr] == [f"conv{i}" for i in range(1, 6)]
    assert not np.array_equal(np.asarray(first), np.asarray(second))
