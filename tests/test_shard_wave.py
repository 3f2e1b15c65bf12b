"""Cooperative sharded-wave execution: the modeled cost model (break
even one row past a full micro-batch, pinned >= 1.5x crossover,
weight-stream amortization), the deterministic in-flight re-shard
(`elastic.reshard_wave`), the row-padding device placement
(`sharding.shard_wave_rows`), the fleet's `shard_waves` lane (trigger,
fallback below data=2, mid-wave kill -> abort -> reshard -> pinned
retry), and bitwise parity of a data=4 cooperative wave with the
single-device unbatched forward."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.accelerator import TPU_V5E
from repro.core.perf_model import (ICI_HOP_LATENCY_S, ShardedWaveCost,
                                   fleet_shard_crossover_batch,
                                   sharded_wave_cost, zoo_wave_cost)
from repro.distributed.elastic import ShardAssignment, reshard_wave
from repro.serve.errors import InsufficientReplicasError, ServeError
from repro.serve.faults import ReplicaChaosConfig, ReplicaFaultInjector
from repro.serve.fleet import FleetServer
from repro.serve.zoo import FIFOPolicy, ZooRequest, build_zoo

RES = {"alexnet": 67}
WIDTH = 0.125


def zoo_models(names=("alexnet-int8",), *, max_batch=2):
    return build_zoo(names, seed=0, in_res=RES, width_mult=WIDTH,
                     max_batch=max_batch)


def img(seed=0, res=67):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((res, res, 3)).astype(np.float32)


def burst(fleet, n, *, model="alexnet-int8", tenant="t", uid0=0):
    """`n` simultaneous arrivals — the cooperative-wave case."""
    reqs = []
    for k in range(n):
        reqs.append(ZooRequest(uid=uid0 + k, model=model,
                               image=img(uid0 + k), tenant=tenant,
                               arrival_s=0.0))
        fleet.submit(reqs[-1])
    return reqs


# -- the cooperative cost model ----------------------------------------------

def test_sharded_wave_cost_invariants():
    c = sharded_wave_cost("alexnet", 16, 4, microbatch=4)
    assert isinstance(c, ShardedWaveCost)
    assert c.shard == 4 and c.data == 4 and c.microbatch == 4
    assert c.total_s == pytest.approx(
        c.conv_s + c.broadcast_s + c.fc_rest_s)
    assert c.fc_s == pytest.approx(c.broadcast_s + c.fc_rest_s)
    assert c.speedup == pytest.approx(c.independent_s / c.total_s)
    # one broadcast replaces ceil(16/4) = 4 independent weight streams
    assert c.amortization == pytest.approx(4.0)
    assert c.weight_stream_bytes * 4 == c.independent_weight_bytes
    # the broadcast is priced on the shared interface plus hop latency
    chip = TPU_V5E
    floor = max(c.weight_stream_bytes / chip.hbm_bandwidth,
                c.weight_stream_bytes / chip.ici_broadcast_bandwidth)
    assert c.broadcast_s == pytest.approx(floor + 3 * ICI_HOP_LATENCY_S)


def test_sharded_wave_cost_validates():
    with pytest.raises(ValueError):
        sharded_wave_cost("alexnet", 0, 4, microbatch=4)
    with pytest.raises(ValueError):
        sharded_wave_cost("alexnet", 4, 0, microbatch=4)


def test_as_wave_cost_preserves_stage_split():
    c = sharded_wave_cost("alexnet", 16, 4, microbatch=4)
    w = c.as_wave_cost()
    assert w.conv_s == pytest.approx(c.conv_s)
    assert w.fc_s == pytest.approx(c.fc_s)
    assert w.total_s == pytest.approx(c.total_s)


@pytest.mark.parametrize("net,bytes_w", [("alexnet", None),
                                         ("vgg16", None),
                                         ("alexnet", 1)])
def test_break_even_is_one_row_past_full_microbatch_wave(net, bytes_w):
    """Sharding breaks even exactly where the fleet's trigger fires: one
    row past a full micro-batch wave (the second independent wave would
    re-stream the FC weights; the broadcast streams them once)."""
    be = fleet_shard_crossover_batch(net, 4, microbatch=4,
                                     threshold=1.0, bytes_w=bytes_w)
    assert be == 5


@pytest.mark.parametrize("net,bytes_w,speedup", [
    ("alexnet", None, 1.912), ("vgg16", None, 1.6566),
    ("alexnet", 1, 1.8182)])
def test_pinned_crossover_batch_and_speedup(net, bytes_w, speedup):
    """The >= 1.5x crossover pin the bench gates: batch 13 at data=4,
    microbatch=4, for fp32 alexnet/vgg16 and int8-weight alexnet."""
    co = fleet_shard_crossover_batch(net, 4, microbatch=4,
                                     bytes_w=bytes_w)
    assert co == 13
    c = sharded_wave_cost(net, co, 4, microbatch=4, bytes_w=bytes_w)
    assert c.speedup >= 1.5
    assert c.speedup == pytest.approx(speedup, rel=1e-3)


def test_crossover_none_when_sharding_never_pays():
    # data=1 is not sharding: no amortization, added hop latency
    assert fleet_shard_crossover_batch(
        "alexnet", 1, microbatch=4) is None


def test_below_microbatch_sharding_loses():
    """Under one micro-batch there is nothing to amortize — independent
    lanes win (speedup < 1), which is why the trigger is `> microbatch`."""
    for b in range(1, 5):
        assert sharded_wave_cost("alexnet", b, 4,
                                 microbatch=4).speedup < 1.0


def test_independent_baseline_matches_zoo_wave_cost():
    c = sharded_wave_cost("alexnet", 16, 4, microbatch=4)
    w = zoo_wave_cost("alexnet", 4)
    assert c.independent_s == pytest.approx(w.conv_s + 4 * w.fc_s)


# -- deterministic in-flight re-shard ----------------------------------------

def test_reshard_wave_deterministic_and_balanced():
    a1 = reshard_wave((5, 3, 9, 1, 7), ("r2", "r0", "r3"))
    a2 = reshard_wave((5, 3, 9, 1, 7), ("r3", "r2", "r0"))
    assert a1 == a2                      # pure function of (uids, set)
    assert isinstance(a1, ShardAssignment)
    assert a1.survivors == ("r0", "r2", "r3")
    assert a1.data == 3
    assert max(a1.shards) - min(a1.shards) <= 1
    assert sorted(u for _, us in a1.assignment for u in us) \
        == [1, 3, 5, 7, 9]
    assert a1.replica_of(5) == "r0"      # first uid -> first survivor
    with pytest.raises(KeyError):
        a1.replica_of(42)


def test_reshard_wave_typed_errors():
    with pytest.raises(InsufficientReplicasError) as ei:
        reshard_wave((1, 2), ())
    assert ei.value.survivors == 0 and ei.value.required == 1
    assert isinstance(ei.value, ServeError)
    with pytest.raises(ValueError):
        reshard_wave((), ("r0",))


def test_reshard_wave_fewer_rows_than_survivors():
    a = reshard_wave((7,), ("r0", "r1", "r2"))
    assert a.assignment == (("r0", (7,)),)   # empty shards are dropped


# -- device placement: padding + committed sharding --------------------------

def test_shard_wave_rows_pads_to_mesh_multiple():
    import jax

    from repro.distributed.sharding import shard_wave_rows

    models = zoo_models()
    fleet = FleetServer(models, n_replicas=2, policy=FIFOPolicy())
    mesh = fleet.mesh()
    d = mesh.devices.size
    x = np.arange(15.0, dtype=np.float32).reshape(5, 3)
    xs, rows = shard_wave_rows(x, mesh)
    assert rows == 5
    assert xs.shape[0] % d == 0 and xs.shape[0] >= 5
    got = np.asarray(jax.device_get(xs))
    assert np.array_equal(got[:5], x)
    assert not got[5:].any()             # zero padding


def test_zoo_sharded_microbatch():
    zm = zoo_models()[0]
    assert zm.sharded_microbatch(4) == 4 * zm.microbatch
    with pytest.raises(ValueError):
        zm.sharded_microbatch(0)


# -- the fleet's shard_waves lane (modeled) ----------------------------------

def test_burst_past_microbatch_forms_cooperative_wave():
    fleet = FleetServer(zoo_models(), n_replicas=4, policy=FIFOPolicy(),
                        shard_waves=True)
    burst(fleet, 6)                      # microbatch=2: 6 > 2 pools
    rep = fleet.serve(execute=False)
    coop = [d for d in rep.decisions if d.sharded]
    assert coop, "fleet-wide backlog past the micro-batch must shard"
    assert coop[0].shards == ("r0", "r1", "r2", "r3")
    assert coop[0].batch > fleet.models["alexnet-int8"].microbatch
    assert len(rep.served) == 6 and rep.unaccounted == ()


def test_shard_waves_off_never_shards():
    fleet = FleetServer(zoo_models(), n_replicas=4, policy=FIFOPolicy())
    burst(fleet, 6)
    rep = fleet.serve(execute=False)
    assert all(not d.sharded for d in rep.decisions)
    assert all(not d.shards for d in rep.decisions)


def test_sharded_schedule_replays_bit_identical():
    logs = []
    for _ in range(2):
        fleet = FleetServer(zoo_models(), n_replicas=4,
                            policy=FIFOPolicy(), shard_waves=True)
        burst(fleet, 7)
        rep = fleet.serve(execute=False)
        logs.append((
            [(d.t_s, d.replica, d.uids, d.batch, d.shards, d.fault)
             for d in rep.decisions],
            [(e.t_s, e.replica, e.kind, e.uids) for e in rep.events],
            {r.uid: r.status for r in rep.requests}))
    assert logs[0] == logs[1]


def test_mesh_below_two_falls_back_typed_not_crash():
    """Satellite invariant: a 1-replica fleet with shard_waves on serves
    the whole burst through the per-replica lane and records a typed
    `shard_fallback` event — never an exception."""
    fleet = FleetServer(zoo_models(), n_replicas=1, policy=FIFOPolicy(),
                        shard_waves=True)
    burst(fleet, 5)
    rep = fleet.serve(execute=False)
    fallbacks = [e for e in rep.events if e.kind == "shard_fallback"]
    assert fallbacks and fallbacks[0].model == "alexnet-int8"
    assert len(rep.served) == 5 and rep.unaccounted == ()
    assert all(not d.sharded for d in rep.decisions)


def test_midwave_kill_aborts_reshards_and_retries_on_survivors():
    """A participant dying inside a cooperative wave aborts the wave
    (`shard_abort`), re-shards its rows over the survivors (`reshard`),
    and the pinned retries serve everything on the shrunk mesh."""
    models = zoo_models()
    half = models[0].sharded_wave_cost(6, 4).total_s / 2
    chaos = ReplicaChaosConfig(kills=(("r2", half),))
    fleet = FleetServer(models, n_replicas=4, policy=FIFOPolicy(),
                        faults=ReplicaFaultInjector(chaos),
                        shard_waves=True)
    burst(fleet, 6)
    rep = fleet.serve(execute=False)
    kinds = [e.kind for e in rep.events]
    assert "shard_abort" in kinds and "reshard" in kinds
    aborted = [d for d in rep.decisions
               if d.sharded and d.fault == "replica_dead"]
    assert aborted and "r2" in aborted[0].shards
    # the retried wave runs on the survivors only
    later = [d for d in rep.decisions if d.t_s > half]
    assert later and all("r2" not in d.shards for d in later)
    assert all(d.replica != "r2" for d in later)
    assert len(rep.served) == 6 and rep.unaccounted == ()
    assert rep.retry_count > 0


def test_midwave_kill_below_two_survivors_still_accounts():
    """Killing down to one survivor mid-wave: the re-shard degrades to
    data=1 (or the fallback lane) but every request stays accounted."""
    models = zoo_models()
    half = models[0].sharded_wave_cost(6, 2).total_s / 2
    chaos = ReplicaChaosConfig(kills=(("r1", half),))
    fleet = FleetServer(models, n_replicas=2, policy=FIFOPolicy(),
                        faults=ReplicaFaultInjector(chaos),
                        shard_waves=True)
    burst(fleet, 6)
    rep = fleet.serve(execute=False)
    assert len(rep.requests) == 6 and rep.unaccounted == ()
    assert all(r.status in ("served", "shed", "quarantined")
               for r in rep.requests)
    assert len(rep.served) == 6          # one survivor still drains all


# -- execution: bitwise parity of the sharded lane ---------------------------

def _assert_bitwise(rep, models, n):
    from repro.models import cnn

    m = models[0]
    assert len(rep.served) == n
    for r in rep.served:
        ref = np.asarray(cnn.cnn_forward(
            m.spec.net, m.params, np.asarray(r.image)[None],
            eng=m.server.engine))[0]
        assert r.done and np.array_equal(np.asarray(r.logits), ref)
        assert np.isfinite(np.asarray(r.logits)).all()


def test_executed_sharded_wave_bitwise_equals_single_device():
    """THE tentpole invariant: one cooperative wave sharded over the
    data mesh serves logits bitwise-equal to the single-device unbatched
    forward (device_put + NamedSharding keeps the per-layer kernels
    byte-stable; a whole-forward jit would not)."""
    models = zoo_models()
    fleet = FleetServer(models, n_replicas=4, policy=FIFOPolicy(),
                        shard_waves=True)
    burst(fleet, 6)
    rep = fleet.serve(execute=True)
    assert any(d.sharded for d in rep.decisions)
    coop_uids = {u for d in rep.decisions if d.sharded for u in d.uids}
    assert len(coop_uids) > models[0].microbatch
    _assert_bitwise(rep, models, 6)


def test_mesh_params_reuse_the_lanes_device_copies():
    """A cooperative wave's mesh-replicated parameters are assembled from
    the participant lanes' committed per-device copies: every shard is a
    lane's own buffer, so no second copy of the weights lands in HBM."""
    import jax

    fleet = FleetServer(zoo_models(), n_replicas=4, policy=FIFOPolicy(),
                        shard_waves=True)
    rids = fleet.replica_ids
    mesh = fleet.shard_mesh(rids)
    params = fleet._mesh_params("alexnet-int8", rids, mesh)
    lanes = [fleet._lane(rid, "alexnet-int8") for rid in rids]
    lane_bufs = {leaf.unsafe_buffer_pointer()
                 for lane in lanes for leaf in jax.tree.leaves(lane.params)}
    for leaf in jax.tree.leaves(params):
        assert leaf.sharding.is_fully_replicated
        assert len(leaf.addressable_shards) == mesh.devices.size
        for shard in leaf.addressable_shards:
            assert shard.data.unsafe_buffer_pointer() in lane_bufs


def test_executed_midwave_kill_resharded_retry_bitwise():
    """Satellite invariant: the re-sharded retry after a mid-wave kill
    is still bitwise-equal on the survivor mesh."""
    models = zoo_models()
    half = models[0].sharded_wave_cost(6, 4).total_s / 2
    chaos = ReplicaChaosConfig(kills=(("r2", half),))
    fleet = FleetServer(models, n_replicas=4, policy=FIFOPolicy(),
                        faults=ReplicaFaultInjector(chaos),
                        shard_waves=True)
    burst(fleet, 6)
    rep = fleet.serve(execute=True)
    assert any(e.kind == "reshard" for e in rep.events)
    assert all(r.replica != "r2" for r in rep.served)
    _assert_bitwise(rep, models, 6)
