"""Multi-tenant model-zoo serving: registry lookup, the compiled-schedule
registry, modeled wave costing, SLO-aware policy scheduling (pinned
deterministic decision logs) and bitwise per-request parity across all
three compiled model variants."""
from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

from repro.configs.registry import ZOO_MODELS, get_zoo_model
from repro.core.perf_model import zoo_wave_cost
from repro.core.schedule import ScheduleRegistry
from repro.serve.zoo import (EDFPolicy, FIFOPolicy, POLICIES,
                             ModelZooServer, ShortestMakespanPolicy,
                             ZooRequest, build_zoo)

RES = {"alexnet": 67, "vgg16": 32}
WIDTH = 0.125

_ZS_PATH = os.path.join(os.path.dirname(__file__), os.pardir,
                        "benchmarks", "zoo_serve.py")


@pytest.fixture(scope="module")
def zs():
    """benchmarks/zoo_serve.py loaded by path (benchmarks is a script
    dir, not a package) — the seeded trace and the modeled-only policy
    runner live there."""
    spec = importlib.util.spec_from_file_location("zoo_serve", _ZS_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reports(zs):
    """One modeled-only (no kernel execution) drain of the seeded fast
    trace per policy — the deterministic schedule every assertion below
    pins."""
    trace = zs.make_trace("fast")
    return {p: zs.run_policy(p, trace, execute=False, refs={}, checks=[])
            for p in ("fifo", "smf", "edf")}


# -- model registry ----------------------------------------------------------

def test_zoo_registry_lookup():
    for name in ("alexnet", "vgg16", "alexnet-int8"):
        spec = get_zoo_model(name)
        assert spec.name == name
        assert spec is ZOO_MODELS[name]
    assert get_zoo_model("alexnet").net == "alexnet"
    assert get_zoo_model("alexnet").weight_bytes == 4
    assert get_zoo_model("vgg16").in_res == 224
    assert get_zoo_model("alexnet-int8").net == "alexnet"
    assert get_zoo_model("alexnet-int8").weight_dtype == "int8"
    assert get_zoo_model("alexnet-int8").weight_bytes == 1


def test_zoo_registry_unknown_name_raises():
    with pytest.raises(KeyError, match="unknown zoo model 'resnet50'"):
        get_zoo_model("resnet50")


def test_build_zoo_refuses_full_width_vgg16(monkeypatch):
    """Under a VMEM budget that cannot hold even a one-row band of
    VGG-16's 224x224 stem, building the zoo raises PlanError naming
    conv1, before any request is admitted — never a wave quarantined at
    serve time.  Parameter shapes stand in for the 0.5 GB of seeded
    weights; only shapes are planned."""
    import jax

    from repro.core.dataflow import PlanError
    from repro.core.engine import DispatchPolicy, Engine
    from repro.models import cnn

    init = cnn.init_cnn
    monkeypatch.setattr(cnn, "init_cnn", lambda *a, **kw: jax.eval_shape(
        lambda: init(*a, **kw)))
    eng = Engine(backend="pallas",
                 policy=DispatchPolicy(vmem_budget=1024 * 1024))
    with pytest.raises(PlanError) as ei:
        build_zoo(("vgg16",), width_mult=1.0, engine=eng)
    assert ei.value.op == "conv1"


def test_build_zoo_builds_full_width_vgg16_with_a_banded_stem(monkeypatch):
    """At the chip's budget full-width VGG-16 builds: conv1 (226x226x3
    padded, 64 out) runs in two bands of 112 rows, conv2-13 on whole
    slabs, and the wave is the admission cap of 8."""
    import jax

    from repro.models import cnn

    init = cnn.init_cnn
    monkeypatch.setattr(cnn, "init_cnn", lambda *a, **kw: jax.eval_shape(
        lambda: init(*a, **kw)))
    model, = build_zoo(("vgg16",), width_mult=1.0)
    assert model.server.in_res == 224 and model.microbatch == 8
    conv, _ = model.server._stage_schedules(8)
    bands = {k.name: (p.band, p.bands) for k, p in conv.conv_entries.items()}
    assert bands.pop("conv1") == (112, 2)
    assert set(bands.values()) == {(0, 1)} and len(bands) == 12


# -- compiled-schedule registry ----------------------------------------------

def test_schedule_registry_keys_and_lookup():
    reg = ScheduleRegistry()
    conv, fc = reg.register("alexnet", dtype_tag="float32", batch=2,
                            in_res=67, width_mult=WIDTH)
    assert ("alexnet", "float32", 2) in reg
    assert reg.stages("alexnet", "float32", 2) == (conv, fc)
    # re-registration is memoized, not duplicated
    assert reg.register("alexnet", dtype_tag="float32", batch=2,
                        in_res=67, width_mult=WIDTH) == (conv, fc)
    assert len(reg) == 1 and reg.keys() == (("alexnet", "float32", 2),)
    with pytest.raises(KeyError, match="no compiled schedule"):
        reg.stages("alexnet", "int8", 2)


# -- modeled wave costing ----------------------------------------------------

def test_zoo_wave_cost_memoized_and_positive():
    a = zoo_wave_cost("alexnet", 4)
    assert a is zoo_wave_cost("alexnet", 4)          # memoized
    assert a.conv_s > 0 and a.fc_s > 0
    assert a.total_s == pytest.approx(a.conv_s + a.fc_s)
    assert a.bottleneck_s == max(a.conv_s, a.fc_s)
    with pytest.raises(ValueError, match="batch"):
        zoo_wave_cost("alexnet", 0)


def test_zoo_wave_cost_knows_the_variants():
    """The scheduler's price sheet must reflect the paper geometry: a
    VGG-16 wave occupies SA-CONV far longer than an AlexNet wave, and the
    int8 variant's FC weight stream is ~4x cheaper than fp32's."""
    b = 4
    alex = zoo_wave_cost("alexnet", b)
    vgg = zoo_wave_cost("vgg16", b)
    int8 = zoo_wave_cost("alexnet", b, bytes_w=1)
    assert vgg.conv_s > 10 * alex.conv_s
    assert alex.fc_s > 3 * int8.fc_s
    assert int8.weight_bytes == 1 and alex.weight_bytes == 4


# -- admission ---------------------------------------------------------------

@pytest.fixture(scope="module")
def small_zoo():
    return build_zoo(("alexnet", "vgg16", "alexnet-int8"), seed=0,
                     in_res=RES, width_mult=WIDTH, max_batch=2)


def _img(net, seed=0):
    rng = np.random.default_rng(seed)
    r = RES[net]
    return rng.standard_normal((r, r, 3)).astype(np.float32)


def test_zoo_submit_unknown_model_and_duplicate_uid(small_zoo):
    zoo = ModelZooServer(small_zoo)
    with pytest.raises(KeyError, match="unknown zoo model 'resnet50'"):
        zoo.submit(ZooRequest(uid=0, model="resnet50",
                              image=_img("alexnet")))
    zoo.submit(ZooRequest(uid=1, model="alexnet", image=_img("alexnet")))
    with pytest.raises(ValueError, match="duplicate request uid 1"):
        zoo.submit(ZooRequest(uid=1, model="vgg16", image=_img("vgg16")))
    assert zoo.pending_count() == 1


def test_zoo_registers_one_schedule_per_variant(small_zoo):
    """The zoo's ScheduleRegistry holds one (net, dtype, microbatch)
    stage-schedule pair per compiled variant — the int8 AlexNet is a
    distinct entry from the fp32 one."""
    zoo = ModelZooServer(small_zoo)
    keys = zoo.registry.keys()
    assert len(keys) == 3
    nets = {(net, tag) for net, tag, _ in keys}
    assert nets == {("alexnet", "float32"), ("alexnet", "int8"),
                    ("vgg16", "float32")}
    for m in small_zoo:
        assert (m.spec.net, m.spec.weight_dtype,
                m.server.microbatch) in zoo.registry


# -- policy scheduling (deterministic modeled time) --------------------------

def test_policy_decision_logs_pinned(reports):
    """The seeded fast trace's decision logs are pure functions of the
    seed — pinned here exactly (model + uids per wave) so any scheduler
    change shows up as a test diff, mirroring the check_bench gate."""
    logs = {p: [(d.model, list(d.uids)) for d in reports[p].decisions]
            for p in reports}
    assert logs["fifo"] == [
        ("alexnet-int8", [0]), ("alexnet-int8", [1]), ("vgg16", [2]),
        ("alexnet-int8", [3]), ("alexnet", [4, 8]),
        ("vgg16", [5, 6, 7, 9]), ("alexnet-int8", [10, 12, 13]),
        ("vgg16", [11]), ("alexnet", [14, 15, 16, 17])]
    assert logs["smf"] == [
        ("alexnet-int8", [0]), ("alexnet-int8", [1]), ("vgg16", [2]),
        ("alexnet-int8", [3]), ("alexnet-int8", [10, 12, 13]),
        ("alexnet", [4, 8]), ("vgg16", [5, 6, 7, 9]),
        ("alexnet", [14, 15, 16, 17]), ("vgg16", [11])]
    # on this trace EDF's deadline ordering lands on the same schedule as
    # SMF (tight deadlines sit on the cheap int8 waves) but for a
    # different reason — both are pinned independently
    assert logs["edf"] == logs["smf"]
    for rep in reports.values():
        assert [d.index for d in rep.decisions] == list(range(9))
        assert sorted(u for d in rep.decisions for u in d.uids) \
            == list(range(18))


def test_edf_strictly_reduces_deadline_misses_vs_fifo(reports):
    """Acceptance: under the seeded Poisson trace, EDF strictly reduces
    the deadline-miss rate vs FIFO."""
    fifo, edf = reports["fifo"], reports["edf"]
    assert fifo.deadline_count == edf.deadline_count == 12
    assert fifo.deadline_misses == 3
    assert edf.deadline_misses < fifo.deadline_misses
    assert edf.miss_rate < fifo.miss_rate


def test_smf_strictly_reduces_mean_latency_vs_fifo(reports):
    """Acceptance: shortest-predicted-makespan-first strictly reduces
    mean latency vs FIFO on the same trace."""
    assert reports["smf"].mean_latency_s < reports["fifo"].mean_latency_s


def test_report_accounting_is_consistent(reports):
    for rep in reports.values():
        assert rep.makespan_s > 0
        assert 0 < rep.conv_utilization <= 1
        assert 0 < rep.fc_utilization <= 1
        assert rep.conv_busy_s == pytest.approx(
            sum(d.conv_s for d in rep.decisions))
        assert [t.tenant for t in rep.per_tenant] \
            == sorted(t.tenant for t in rep.per_tenant)
        for t in rep.per_tenant:
            assert t.p50_s <= t.p95_s <= t.p99_s
            assert 0 <= t.misses <= t.deadlines <= t.n
        assert "waves" in rep.summary()
        # every request was stamped with a causally-sane interval
        for r in rep.requests:
            assert r.arrival_s <= r.dispatch_s < r.finish_s


def test_policies_table_is_complete():
    assert set(POLICIES) == {"fifo", "smf", "edf"}
    assert isinstance(POLICIES["fifo"](), FIFOPolicy)
    assert isinstance(POLICIES["smf"](), ShortestMakespanPolicy)
    assert isinstance(POLICIES["edf"](), EDFPolicy)


def test_edf_wave_order_tightest_deadline_first():
    reqs = [ZooRequest(uid=0, model="m", image=None, deadline_s=None),
            ZooRequest(uid=1, model="m", image=None, deadline_s=5.0),
            ZooRequest(uid=2, model="m", image=None, deadline_s=1.0)]
    assert [r.uid for r in EDFPolicy().wave_order(reqs)] == [2, 1, 0]


# -- end-to-end: real kernels, bitwise parity --------------------------------

def test_zoo_serving_bitwise_parity_all_variants(small_zoo):
    """Acceptance: a mixed trace across all three compiled variants
    (incl. the int8 AlexNet) serves every request with logits bitwise
    equal to that model's single-model unbatched forward, whatever wave
    coalescing the policy chose."""
    import jax.numpy as jnp

    from repro.models import cnn

    zoo = ModelZooServer(small_zoo, policy=ShortestMakespanPolicy())
    reqs = []
    uid = 0
    for model in ("alexnet", "vgg16", "alexnet-int8", "alexnet",
                  "alexnet-int8"):
        net = small_zoo[0].spec.net if model != "vgg16" else "vgg16"
        r = ZooRequest(uid=uid, model=model, image=_img(net, seed=uid),
                       tenant=f"t{uid % 2}", arrival_s=uid * 1e-4)
        zoo.submit(r)
        reqs.append(r)
        uid += 1
    report = zoo.serve()
    assert zoo.pending_count() == 0
    assert len(report.requests) == 5
    models = {m.name: m for m in small_zoo}
    for r in report.requests:
        assert r.done and r.logits is not None
        m = models[r.model]
        ref = cnn.cnn_forward(m.spec.net, m.params,
                              jnp.asarray(r.image)[None],
                              eng=m.server.engine)
        np.testing.assert_array_equal(np.asarray(ref)[0], r.logits)
    # serving again with nothing queued is a no-op report
    empty = zoo.serve()
    assert empty.requests == () and empty.decisions == ()
