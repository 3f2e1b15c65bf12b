"""Sharded-fleet serving: placement policies, single-replica
equivalence with the zoo scheduler, replica death (in-flight wave loss,
queued-drain to peers, elastic replan, heartbeat deregistration),
partitioned heartbeats (suspect -> rejoin), transient device stalls
(straggler + timeout retry), the no-survivors floor, replay determinism,
and bitwise execution parity across replica lanes."""
from __future__ import annotations

import numpy as np
import pytest

from repro.serve.errors import (InsufficientReplicasError,
                                ReplicaLostError, ServeError,
                                WaveTimeoutError)
from repro.serve.faults import (ChaosConfig, FaultInjector,
                                ReplicaChaosConfig, ReplicaFaultInjector)
from repro.serve.fleet import (PLACEMENTS, FleetServer,
                               LeastLoadedPlacement, ReplicaView,
                               RoundRobinPlacement)
from repro.serve.zoo import (AdmissionConfig, FIFOPolicy, ModelZooServer,
                             RecoveryConfig, ZooRequest, build_zoo)

RES = {"alexnet": 67}
WIDTH = 0.125

TERMINAL = ("served", "shed", "quarantined")


def zoo_models(names=("alexnet-int8",), *, max_batch=2):
    return build_zoo(names, seed=0, in_res=RES, width_mult=WIDTH,
                     max_batch=max_batch)


def fresh_fleet(names=("alexnet-int8",), *, n_replicas=2, max_batch=2,
                **kw):
    """A small fresh fleet per test (servers consume uids for life)."""
    return FleetServer(zoo_models(names, max_batch=max_batch),
                       n_replicas=n_replicas, policy=FIFOPolicy(), **kw)


def img(seed=0, res=67):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((res, res, 3)).astype(np.float32)


def submit_n(fleet, n, *, model="alexnet-int8", tenant="t",
             spacing=1e-3, uid0=0):
    reqs = []
    for k in range(n):
        reqs.append(ZooRequest(uid=uid0 + k, model=model,
                               image=img(uid0 + k), tenant=tenant,
                               arrival_s=k * spacing))
        fleet.submit(reqs[-1])
    return reqs


def assert_accounted(report, n):
    assert len(report.requests) == n
    assert report.unaccounted == ()
    for r in report.requests:
        assert r.status in TERMINAL
        if r.status == "served":
            assert r.error is None and r.finish_s is not None
            assert r.replica is not None
        else:
            assert isinstance(r.error, ServeError)


def wave_s(fleet, model="alexnet-int8", batch=1):
    return fleet.models[model].wave_cost(batch).total_s


# -- construction / submit contract ------------------------------------------

def test_fleet_constructor_validates():
    models = zoo_models()
    with pytest.raises(ValueError):
        FleetServer(models, n_replicas=0)
    with pytest.raises(ValueError):
        FleetServer([])
    f = FleetServer(models, n_replicas=3)
    assert f.replica_ids == ("r0", "r1", "r2")


def test_submit_contract_matches_zoo():
    fleet = fresh_fleet()
    fleet.submit(ZooRequest(uid=0, model="alexnet-int8", image=img(0),
                            tenant="t", arrival_s=0.0))
    with pytest.raises(KeyError):
        fleet.submit(ZooRequest(uid=1, model="nope", image=img(1),
                                tenant="t", arrival_s=0.0))
    with pytest.raises(ValueError):
        fleet.submit(ZooRequest(uid=0, model="alexnet-int8",
                                image=img(0), tenant="t", arrival_s=0.0))
    stale = ZooRequest(uid=2, model="alexnet-int8", image=img(2),
                       tenant="t", arrival_s=2.0, deadline_s=1.0)
    assert fleet.submit(stale) is False
    assert stale.status == "shed"
    rep = fleet.serve(execute=False)
    assert_accounted(rep, 2)
    assert len(rep.served) == 1 and len(rep.shed) == 1


def test_empty_fleet_serves_empty_report():
    rep = fresh_fleet().serve(execute=False)
    assert rep.requests == () and rep.decisions == ()
    assert rep.makespan_s == 0.0 and rep.throughput_rps == 0.0


# -- placement ---------------------------------------------------------------

def test_round_robin_placement_rotates():
    views = [ReplicaView(f"r{i}", i, 0, 0.0, 0.0) for i in range(3)]
    rr = RoundRobinPlacement()
    req = ZooRequest(uid=0, model="m", image=None, tenant="t",
                     arrival_s=0.0)
    assert [rr.place(0.0, views, req) for _ in range(4)] \
        == ["r0", "r1", "r2", "r0"]


def test_least_loaded_placement_prefers_cheapest_backlog():
    ll = LeastLoadedPlacement()
    req = ZooRequest(uid=0, model="m", image=None, tenant="t",
                     arrival_s=0.0)
    views = [ReplicaView("r0", 0, 2, 5e-4, 0.0),
             ReplicaView("r1", 1, 0, 0.0, 1e-4),
             ReplicaView("r2", 2, 1, 2e-4, 0.0)]
    assert ll.place(0.0, views, req) == "r1"
    # ties break by queue depth then index, deterministically
    even = [ReplicaView("r0", 0, 1, 1e-4, 0.0),
            ReplicaView("r1", 1, 0, 1e-4, 0.0)]
    assert ll.place(0.0, even, req) == "r1"
    assert "least-loaded" in PLACEMENTS and "round-robin" in PLACEMENTS


def test_fleet_spreads_simultaneous_arrivals():
    fleet = fresh_fleet(n_replicas=2)
    submit_n(fleet, 4, spacing=0.0)
    rep = fleet.serve(execute=False)
    assert_accounted(rep, 4)
    used = {d.replica for d in rep.decisions}
    assert used == {"r0", "r1"}          # both replicas took work


# -- single-replica equivalence with the zoo ---------------------------------

WAVE_CHAOS = dict(
    faults=ChaosConfig(seed=2, dispatch_fail_rate=0.15, corrupt_rate=0.15,
                       stall_rate=0.25, stall_factors=(4.0, 24.0)),
    admission=AdmissionConfig(max_queue=3, predictive_shedding=True))


@pytest.mark.parametrize("chaos", [None, WAVE_CHAOS],
                         ids=["no-chaos", "wave-chaos"])
def test_single_replica_fleet_schedule_equals_zoo(chaos):
    """One replica: the fleet IS the zoo — same decisions (time, model,
    uids, batch, stage costs, faults), same events, same terminal
    statuses, same makespan — with no chaos, and under wave chaos
    (dispatch, corrupt and stall faults, bounded queues, predictive
    shedding and the int8 sibling's degraded fallback)."""
    names = ("alexnet", "alexnet-int8")

    def kw():
        if chaos is None:
            return {}
        return dict(faults=FaultInjector(chaos["faults"]),
                    admission=chaos["admission"])

    fleet = fresh_fleet(names=names, n_replicas=1, **kw())
    zoo = ModelZooServer(zoo_models(names), policy=FIFOPolicy(), **kw())
    fp32 = fleet.models["alexnet"].wave_cost(1).total_s
    for srv in (fleet, zoo):
        for k in range(12):
            model = "alexnet" if k % 3 else "alexnet-int8"
            srv.submit(ZooRequest(uid=k, model=model, image=img(k),
                                  tenant=f"t{k % 2}", arrival_s=k * 2e-5,
                                  deadline_s=k * 2e-5 + (0.9 + k % 4)
                                  * fp32))
    frep = fleet.serve(execute=False)
    zrep = zoo.serve(execute=False)
    assert frep.decisions == zrep.decisions
    assert frep.events == zrep.events
    assert {r.uid: r.status for r in frep.requests} \
        == {r.uid: r.status for r in zrep.requests}
    assert frep.makespan_s == zrep.makespan_s
    assert all(d.replica == "r0" for d in frep.decisions)
    if chaos is not None:
        kinds = {e.kind for e in zrep.events}
        assert {"retry", "shed", "degrade"} <= kinds
        assert {d.fault for d in zrep.decisions} - {"none"}


def test_one_replica_zoo_runs_every_wave_on_the_model_server():
    """The one-chip zoo executes on ``ZooModel.server`` itself: no
    second lane, no second copy of the weights."""
    models = zoo_models()
    srv = models[0].server
    zoo = ModelZooServer(models, policy=FIFOPolicy())
    step_wave, calls = srv.step_wave, []

    def counted():
        calls.append(1)
        return step_wave()

    srv.step_wave = counted
    submit_n(zoo, 3, spacing=0.0)
    rep = zoo.serve()
    assert len(rep.served) == 3 and len(calls) == len(rep.decisions) == 2
    assert zoo._lanes == {"r0": {"alexnet-int8": srv}}
    assert zoo._lane("r0", "alexnet-int8") is srv


def test_multi_replica_never_slower_than_one():
    traces = []
    for nr in (1, 2):
        fleet = fresh_fleet(n_replicas=nr)
        submit_n(fleet, 8, spacing=0.0)
        traces.append(fleet.serve(execute=False).makespan_s)
    assert traces[1] <= traces[0]


# -- replica death -----------------------------------------------------------

def test_kill_in_flight_wave_retries_on_peer():
    """r0 dies mid-wave: the wave is lost (replica_dead), its request
    retries on r1 and is served there."""
    c = wave_s(fresh_fleet())
    chaos = ReplicaChaosConfig(kills=(("r0", 0.5 * c),))
    fleet = fresh_fleet(n_replicas=2,
                        faults=ReplicaFaultInjector(chaos))
    submit_n(fleet, 1)
    rep = fleet.serve(execute=False)
    assert_accounted(rep, 1)
    r = rep.requests[0]
    assert r.status == "served" and r.replica == "r1" and r.retries == 1
    kinds = [e.kind for e in rep.events]
    assert "replica_dead" in kinds and "kill" in kinds \
        and "retry" in kinds
    dead = [d for d in rep.decisions if d.fault == "replica_dead"]
    assert len(dead) == 1 and dead[0].replica == "r0"
    states = {s.replica: s.state for s in rep.per_replica}
    assert states == {"r0": "dead", "r1": "alive"}


def test_kill_drains_queue_to_surviving_peer():
    """Everything placed on the dying replica — queued waves included —
    ends up served by the survivor; replan proposes the shrunk mesh."""
    chaos = ReplicaChaosConfig(kills=(("r0", 1e-9),))
    fleet = fresh_fleet(n_replicas=2,
                        faults=ReplicaFaultInjector(chaos))
    submit_n(fleet, 6, spacing=0.0)
    rep = fleet.serve(execute=False)
    assert_accounted(rep, 6)
    assert len(rep.served) == 6
    assert all(r.replica == "r1" for r in rep.served)
    assert len(rep.drained_uids) >= 1
    assert all(u in {r.uid for r in rep.served}
               for u in rep.drained_uids)
    # the mesh plan history shrank after the death
    assert rep.mesh_plans[0][1] == 2          # initial data degree
    post = [p for p in rep.mesh_plans[1:] if "dead" in p[3]]
    assert post and post[0][1] == 1
    # nothing ever dispatched on the corpse
    assert all(d.replica == "r1" or d.fault == "replica_dead"
               for d in rep.decisions)


def test_all_replicas_dead_quarantines_with_typed_errors():
    """No survivors: the fleet reports instead of wedging — every
    request quarantined with ReplicaLostError, and the failed replan is
    an event, not an exception."""
    chaos = ReplicaChaosConfig(kills=(("r0", 1e-9),))
    fleet = fresh_fleet(n_replicas=1,
                        faults=ReplicaFaultInjector(chaos))
    submit_n(fleet, 3, spacing=1e-4)
    rep = fleet.serve(execute=False)
    assert_accounted(rep, 3)
    assert len(rep.quarantined) == 3
    assert all(isinstance(r.error, ReplicaLostError)
               for r in rep.quarantined)
    assert any(e.kind == "replan_failed" for e in rep.events)


# -- partitioned heartbeats --------------------------------------------------

def test_partition_suspects_then_rejoins():
    """An idle replica whose heartbeats drop for a window is suspected
    after the deadline and rejoins when the partition heals — and the
    fleet serves everything throughout."""
    chaos = ReplicaChaosConfig(partitions=(("r1", 1e-4, 5e-4),))
    rec = RecoveryConfig(heartbeat_timeout_s=1e-4)
    fleet = fresh_fleet(n_replicas=2,
                        faults=ReplicaFaultInjector(chaos), recovery=rec)
    # arrivals straddle the window so the loop visits its milestones
    submit_n(fleet, 4, spacing=2e-4)
    rep = fleet.serve(execute=False)
    assert_accounted(rep, 4)
    assert len(rep.served) == 4
    suspects = [e for e in rep.events if e.kind == "suspect"]
    rejoins = [e for e in rep.events if e.kind == "rejoin"]
    assert suspects and suspects[0].replica == "r1"
    assert suspects[0].t_s == pytest.approx(2e-4)   # start + timeout
    assert rejoins and rejoins[0].replica == "r1"
    assert rejoins[0].t_s >= 5e-4                   # after the heal
    # both transitions replanned the mesh
    whys = [p[3] for p in rep.mesh_plans]
    assert any("suspect" in w for w in whys)
    assert any("rejoined" in w for w in whys)


# -- transient stalls --------------------------------------------------------

def test_hard_stall_times_out_retries_then_quarantines():
    """Every attempt stalls past the timeout factor: retries exhaust and
    the request quarantines with WaveTimeoutError — zero unaccounted."""
    chaos = ReplicaChaosConfig(seed=5, stall_rate=1.0,
                               stall_factors=(24.0,))
    rec = RecoveryConfig(max_retries=1, wave_timeout_factor=8.0)
    fleet = fresh_fleet(n_replicas=1,
                        faults=ReplicaFaultInjector(chaos), recovery=rec)
    submit_n(fleet, 1)
    rep = fleet.serve(execute=False)
    assert_accounted(rep, 1)
    r = rep.requests[0]
    assert r.status == "quarantined" and r.retries == 2
    assert isinstance(r.error, WaveTimeoutError)
    assert [d.fault for d in rep.decisions] == ["timeout", "timeout"]
    # aborted waves still advanced the replica's clocks (capped)
    assert all(d.stall_factor == 24.0 for d in rep.decisions)


def test_mild_stall_serves_late_with_stall_annotation():
    chaos = ReplicaChaosConfig(seed=5, stall_rate=1.0,
                               stall_factors=(3.0,))
    fleet = fresh_fleet(n_replicas=1,
                        faults=ReplicaFaultInjector(chaos))
    submit_n(fleet, 2)
    rep = fleet.serve(execute=False)
    assert_accounted(rep, 2)
    assert len(rep.served) == 2
    assert all(d.fault == "stall" and d.stall_factor == 3.0
               for d in rep.decisions)


# -- determinism -------------------------------------------------------------

def test_modeled_schedule_replays_bit_identical():
    chaos = ReplicaChaosConfig(seed=9, stall_rate=0.3,
                               stall_factors=(3.0, 24.0),
                               kills=(("r1", 3e-4),),
                               partitions=(("r0", 5e-4, 9e-4),))
    rec = RecoveryConfig(heartbeat_timeout_s=1e-4)
    logs = []
    for _ in range(2):
        fleet = fresh_fleet(n_replicas=3,
                            faults=ReplicaFaultInjector(chaos),
                            recovery=rec)
        submit_n(fleet, 8, spacing=5e-5)
        rep = fleet.serve(execute=False)
        assert_accounted(rep, 8)
        logs.append((
            [(d.t_s, d.replica, d.model, d.uids, d.batch, d.fault,
              d.stall_factor) for d in rep.decisions],
            [(e.t_s, e.replica, e.kind, e.uids) for e in rep.events],
            {r.uid: r.status for r in rep.requests},
            rep.mesh_plans))
    assert logs[0] == logs[1]


# -- execution: lanes, parity, devices --------------------------------------

def test_fleet_mesh_over_distinct_devices():
    import jax
    fleet = fresh_fleet(n_replicas=4)
    mesh = fleet.mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == min(4, len(jax.devices()))


def test_executed_fleet_parity_with_single_device_forward():
    """Served logits are bitwise equal to the model's unbatched
    single-device forward, whichever replica lane served them."""
    from repro.models import cnn

    models = zoo_models()
    fleet = FleetServer(models, n_replicas=2, policy=FIFOPolicy())
    submit_n(fleet, 3, spacing=0.0)
    rep = fleet.serve(execute=True)
    assert_accounted(rep, 3)
    assert len(rep.served) == 3
    assert {r.replica for r in rep.served} == {"r0", "r1"}
    m = models[0]
    for r in rep.served:
        ref = np.asarray(cnn.cnn_forward(
            m.spec.net, m.params, np.asarray(r.image)[None],
            eng=m.server.engine))[0]
        assert r.done and np.array_equal(np.asarray(r.logits), ref)
        assert np.isfinite(np.asarray(r.logits)).all()


def test_executed_kill_still_serves_survivors_bitwise():
    """Real kernels + a replica death: the drained/retried requests'
    logits still match the single-device forward bitwise."""
    from repro.models import cnn

    models = zoo_models()
    chaos = ReplicaChaosConfig(kills=(("r0", 1e-9),))
    fleet = FleetServer(models, n_replicas=2, policy=FIFOPolicy(),
                        faults=ReplicaFaultInjector(chaos))
    submit_n(fleet, 2, spacing=0.0)
    rep = fleet.serve(execute=True)
    assert_accounted(rep, 2)
    assert len(rep.served) == 2
    m = models[0]
    for r in rep.served:
        assert r.replica == "r1"
        ref = np.asarray(cnn.cnn_forward(
            m.spec.net, m.params, np.asarray(r.image)[None],
            eng=m.server.engine))[0]
        assert np.array_equal(np.asarray(r.logits), ref)


# -- fleet error types -------------------------------------------------------

def test_fleet_error_types():
    e = ReplicaLostError("gone", uid=3, model="m", replica="r2")
    assert isinstance(e, ServeError)
    assert e.replica == "r2" and "replica=r2" in str(e)
    ie = InsufficientReplicasError("too few", survivors=1, required=4)
    assert isinstance(ie, ServeError)
    assert ie.survivors == 1 and ie.required == 4
