"""Sharded-fleet benchmark — replica-level fault tolerance for the zoo
serving plane (:class:`~repro.serve.fleet.FleetServer`).

``zoo_serve.py`` pins the single-pipeline scheduler and
``chaos_serve.py`` pins its wave-level recovery; this benchmark pins the
**fleet**: N data-parallel replicas of the same model zoo splitting one
admitted request stream, and the replica-granular fault plane that keeps
the fleet serving when replicas die.  Five configurations share one
seeded compute-bound trace:

* **healthy_r1 / healthy_r2 / healthy_r4** — no chaos, least-loaded
  placement, modeled-only: the throughput-scaling story (and the
  ``healthy_r1`` schedule doubles as the zoo-equivalence witness — one
  replica's fleet decisions must equal ``ModelZooServer``'s, bitwise);
* **round_robin_r4** — same healthy trace under the baseline placement,
  so the load-aware policy has a pinned comparison;
* **chaos_r4** — executed on the real kernels: replica ``r1`` dies
  mid-trace (its in-flight wave is lost and retried on a peer, its
  queue drains), replica ``r2``'s heartbeats are partitioned for a
  window (suspect -> drain -> rejoin), and seeded transient stalls trip
  the per-replica straggler/timeout machinery throughout;
* **sharded_r4** — the same payload stream collapsed to a ``t=0``
  burst (the cooperative case: a batch larger than one replica's
  micro-batch lands at once) with ``shard_waves=True``: when a model's
  fleet-wide backlog exceeds one replica's planner micro-batch, the
  fleet cuts ONE cooperative wave of up to ``data x bb`` rows and
  executes it across the healthy-replica mesh (``jax.device_put`` with
  a ``NamedSharding`` over the ``("data",)`` axis) instead of fanning
  independent per-replica waves.  Executed on the real kernels with a
  bitwise-parity gate against the single-device unbatched forward;
* **sharded_chaos_r4** — the sharded burst with a replica killed
  mid-cooperative-wave: the wave aborts (``shard_abort``), its rows are
  re-sharded over the survivors (``elastic.reshard_wave`` ->
  ``reshard`` event), and the retries honor the pinned assignment —
  again executed, again gated bitwise.

The **modeled sharded section** pins the cooperative cost model
(:func:`repro.core.perf_model.sharded_wave_cost`): per-model speedup
curves over batch at ``data=4``, the break-even batch (5 — one row past
a full micro-batch wave, exactly the shard trigger), the >= 1.5x
crossover batch (13), and the weight-stream amortization (4.0x at a
full ``data x bb`` wave).

Acceptance invariants recorded as internal checks (process exits
nonzero on failure): zero unaccounted requests in every configuration;
healthy throughput scaling >= 1.5x from 1 to 4 replicas on the modeled
fleet clock; at least one request drained off the dead replica is
ultimately served by a peer; ``elastic.replan`` proposes a shrunk mesh
after the death and nothing ever dispatches on the dead replica again;
the partition produces a suspect *and* a rejoin; the single-replica
fleet schedule is identical to the zoo scheduler's; the modeled
schedule replays bit-for-bit; and every served logit row is bitwise
equal to its model's single-device unbatched forward (no non-finite
values), no matter which replica or how many retries served it.

The modeled schedule never reads the JAX device count — on the CPU, run
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to spread the
execution lanes over a real multi-device mesh; the artifact is identical
either way.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python benchmarks/fleet_serve.py --fast --out BENCH_sharded.json
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

try:                                    # package import (benchmarks.run)
    from benchmarks.timing import poisson_arrivals, \
        raise_on_failed_checks, run_emit_cli, seeded_payloads
except ImportError:                     # direct script execution
    from timing import poisson_arrivals, raise_on_failed_checks, \
        run_emit_cli, seeded_payloads

Row = tuple[str, float, str]

#: Execution geometry — identical to zoo_serve/chaos_serve: width-scaled
#: models (interpret-mode Pallas on CPU), full-geometry cost model.
WIDTH_MULT = 0.125
IN_RES = {"alexnet": 67, "vgg16": 32}
MAX_BATCH = 4
MODELS = ("alexnet", "vgg16", "alexnet-int8")

#: The seeded trace per tier.  Arrival rates are far above one
#: replica's service rate, so the stream is **compute-bound** — that is
#: what makes replica scaling visible (an arrival-limited trace would
#: cap the speedup at the arrival span no matter how many replicas).
TRACE_TIERS = {
    "fast": {
        "seed": 0,
        "tenants": [
            # (tenant, model, n, rate_hz)
            ("web", "alexnet", 8, 60000.0),
            ("batch", "vgg16", 8, 40000.0),
            ("rt", "alexnet-int8", 6, 50000.0),
        ],
    },
    "full": {
        "seed": 0,
        "tenants": [
            ("web", "alexnet", 14, 60000.0),
            ("batch", "vgg16", 12, 40000.0),
            ("rt", "alexnet-int8", 10, 50000.0),
        ],
    },
}

#: The replica-granular chaos plan for chaos_r4: r1 dies mid-trace
#: (during the heavy early waves, so an in-flight wave is lost), r2's
#: heartbeats drop for a window long enough to trip the failure
#: detector and heal before the drain ends, and seeded transient stalls
#: (one below the timeout factor, one above) run throughout.
CHAOS = {
    "seed": 11,
    "stall_rate": 0.2,
    "stall_factors": (3.0, 24.0),
    "kills": (("r1", 2.5e-4),),
    "partitions": (("r2", 4.0e-4, 1.1e-3),),
}

#: The sharded-chaos plan: r2 dies inside the first cooperative wave
#: (sharded waves start dispatching once the fleet-wide backlog of a
#: model passes the micro-batch, well before 300us on this trace and
#: finish after it), forcing the abort -> reshard -> pinned-retry path.
SHARD_CHAOS = {"kills": (("r2", 3.0e-4),)}

#: Cooperative-wave geometry for the modeled sharded section.
SHARD_DATA = 4
SHARD_THRESHOLD = 1.5

#: Recovery policy (zoo defaults plus a heartbeat deadline shorter than
#: the partition window, so the suspect verdict actually fires).
RECOVERY = {
    "max_retries": 2,
    "wave_timeout_factor": 8.0,
    "heartbeat_timeout_s": 2.0e-4,
}

#: Fleet shape shared by every configuration.
FLEET = {"mesh_model_parallel": 1, "mesh_global_batch": 64,
         "mesh_pod_size": 64}

#: generate-mode knob (benchmarks/check_bench.py): the modeled fleet
#: schedule, statuses, event log and accounting are
#: execution-independent, so the regression gate regenerates with
#: execution (and the parity checks) off.
EXECUTE = True


def make_trace(tier: str) -> list[dict]:
    """The seeded compute-bound request stream (same plain-dict shape as
    zoo_serve/chaos_serve)."""
    cfg = TRACE_TIERS[tier]
    raw = []
    for ti, (tenant, model, n, rate) in enumerate(cfg["tenants"]):
        net = "vgg16" if model == "vgg16" else "alexnet"
        res = IN_RES[net]
        arrivals = poisson_arrivals(n, rate, seed=cfg["seed"] + ti)
        images = seeded_payloads(n, (res, res, 3),
                                 seed=300 + cfg["seed"] + ti)
        for a, img in zip(arrivals, images):
            raw.append({"tenant": tenant, "model": model, "arrival_s": a,
                        "deadline_s": None, "image": img})
    raw.sort(key=lambda r: (r["arrival_s"], r["tenant"]))
    for uid, r in enumerate(raw):
        r["uid"] = uid
    return raw


def _models():
    from repro.serve.zoo import build_zoo
    return build_zoo(MODELS, seed=0, in_res=IN_RES,
                     width_mult=WIDTH_MULT, max_batch=MAX_BATCH)


def build_fleet(*, n_replicas: int, chaos: dict | bool = False,
                placement: str = "least-loaded",
                shard_waves: bool = False):
    from repro.serve.faults import (ReplicaChaosConfig,
                                    ReplicaFaultInjector)
    from repro.serve.fleet import PLACEMENTS, FleetServer
    from repro.serve.zoo import FIFOPolicy, RecoveryConfig

    plan = CHAOS if chaos is True else chaos
    faults = ReplicaFaultInjector(ReplicaChaosConfig(**plan)) \
        if plan else None
    return FleetServer(
        _models(), n_replicas=n_replicas, policy=FIFOPolicy(),
        placement=PLACEMENTS[placement](), faults=faults,
        recovery=RecoveryConfig(**RECOVERY), shard_waves=shard_waves,
        **FLEET)


def run_config(trace: list[dict], *, n_replicas: int,
               chaos: dict | bool = False,
               placement: str = "least-loaded",
               shard_waves: bool = False, execute: bool = False):
    """One full fleet drain; returns the FleetReport."""
    from repro.serve.zoo import ZooRequest

    fleet = build_fleet(n_replicas=n_replicas, chaos=chaos,
                        placement=placement, shard_waves=shard_waves)
    for r in trace:
        fleet.submit(ZooRequest(uid=r["uid"], model=r["model"],
                                image=r["image"], tenant=r["tenant"],
                                arrival_s=r["arrival_s"],
                                deadline_s=r["deadline_s"]))
    return fleet.serve(execute=execute)


def served_refs(report) -> dict[int, np.ndarray]:
    """uid -> unbatched single-device forward through the request's
    model: the cross-replica parity reference."""
    import jax.numpy as jnp

    from repro.models import cnn

    models = {m.name: m for m in _models()}
    refs = {}
    for r in report.served:
        m = models[r.model]
        y = cnn.cnn_forward(m.spec.net, m.params,
                            jnp.asarray(np.asarray(r.image))[None],
                            eng=m.server.engine)
        refs[r.uid] = np.asarray(y)[0]
    return refs


def _decision_key(d) -> tuple:
    # getattr: zoo decisions (the healthy_r1 equivalence witness) have
    # no shards field — a single pipeline can never shard a wave
    return (round(d.t_s * 1e9), d.model, d.uids, d.batch,
            round(d.conv_s * 1e9), round(d.fc_s * 1e9),
            tuple(getattr(d, "shards", ())))


def _report_doc(report) -> dict:
    """The deterministic (modeled-time, device-count-independent) slice
    of one fleet drain."""
    us = 1e6
    return {
        "decisions": [{
            "index": d.index, "t_us": round(d.t_s * us, 3),
            "replica": d.replica, "model": d.model,
            "uids": list(d.uids), "batch": d.batch,
            "conv_us": round(d.conv_s * us, 3),
            "fc_us": round(d.fc_s * us, 3),
            "fault": d.fault, "stall_factor": d.stall_factor,
            "shards": list(d.shards),
        } for d in report.decisions],
        "events": [{
            "t_us": round(e.t_s * us, 3), "replica": e.replica,
            "kind": e.kind, "uids": list(e.uids), "model": e.model,
        } for e in report.events],
        "statuses": {str(r.uid): r.status for r in report.requests},
        "replicas": {str(r.uid): r.replica for r in report.served},
        "per_replica": [{
            "replica": s.replica, "state": s.state, "waves": s.waves,
            "served": s.served, "busy_us": round(s.busy_s * us, 3),
            "drained_away": s.drained_away,
        } for s in report.per_replica],
        "mesh_plans": [{
            "t_us": round(t * us, 3), "data": data, "wasted": wasted,
            "why": why,
        } for t, data, wasted, why in report.mesh_plans],
        "served": len(report.served),
        "shed": len(report.shed),
        "quarantined": len(report.quarantined),
        "unaccounted": len(report.unaccounted),
        "retry_count": report.retry_count,
        "drained_uids": list(report.drained_uids),
        "makespan_us": round(report.makespan_s * us, 3),
    }


def _accounting_checks(name: str, report, trace, checks: list) -> None:
    statuses = [r.status for r in report.requests]
    counts = {s: statuses.count(s) for s in
              ("served", "shed", "quarantined")}
    checks.append({
        "name": f"accounting/{name}/zero_unaccounted",
        "passed": (len(report.unaccounted) == 0
                   and len(report.requests) == len(trace)
                   and sum(counts.values()) == len(trace)),
        "detail": f"{counts} of {len(trace)} requests, "
                  f"{len(report.unaccounted)} unaccounted"})


def emit(out_path: str = "BENCH_sharded.json", *, tier: str = "fast"
         ) -> list[Row]:
    """Run the fleet benchmark, write the JSON artifact, return CSV rows
    for benchmarks/run.py."""
    checks: list[dict] = []
    trace = make_trace(tier)

    t0 = time.perf_counter()
    healthy = {nr: run_config(trace, n_replicas=nr) for nr in (1, 2, 4)}
    rr4 = run_config(trace, n_replicas=4, placement="round-robin")
    chaos4 = run_config(trace, n_replicas=4, chaos=True,
                        execute=EXECUTE)
    replay = run_config(trace, n_replicas=4, chaos=True)
    # cooperative waves need a backlog wider than one replica's
    # micro-batch to pool while peers are free — the same payloads as a
    # t=0 burst (a staggered Poisson stream drains one request at a
    # time onto whichever replica frees first, so nothing ever pools)
    burst = [dict(r, arrival_s=0.0) for r in trace]
    sharded4 = run_config(burst, n_replicas=4, shard_waves=True,
                          execute=EXECUTE)
    sharded_replay = run_config(burst, n_replicas=4, shard_waves=True)
    shard_chaos4 = run_config(burst, n_replicas=4, chaos=SHARD_CHAOS,
                              shard_waves=True, execute=EXECUTE)
    # the zoo-equivalence witness: same trace through the single-pipeline
    # scheduler this fleet generalizes
    from repro.serve.zoo import FIFOPolicy, ModelZooServer, ZooRequest
    zoo = ModelZooServer(_models(), policy=FIFOPolicy())
    for r in trace:
        zoo.submit(ZooRequest(uid=r["uid"], model=r["model"],
                              image=r["image"], tenant=r["tenant"],
                              arrival_s=r["arrival_s"],
                              deadline_s=r["deadline_s"]))
    zoo_rep = zoo.serve(execute=False)
    wall_s = time.perf_counter() - t0

    docs = {f"healthy_r{nr}": _report_doc(rep)
            for nr, rep in healthy.items()}
    docs["round_robin_r4"] = _report_doc(rr4)
    docs["chaos_r4"] = _report_doc(chaos4)
    docs["sharded_r4"] = _report_doc(sharded4)
    docs["sharded_chaos_r4"] = _report_doc(shard_chaos4)

    for name, rep in [("healthy_r1", healthy[1]),
                      ("healthy_r2", healthy[2]),
                      ("healthy_r4", healthy[4]),
                      ("round_robin_r4", rr4), ("chaos_r4", chaos4),
                      ("sharded_r4", sharded4),
                      ("sharded_chaos_r4", shard_chaos4)]:
        _accounting_checks(name, rep, trace, checks)

    scaling = healthy[1].makespan_s / healthy[4].makespan_s
    checks.append({
        "name": "fleet/healthy_scaling_1_to_4_at_least_1p5x",
        "passed": scaling >= 1.5,
        "detail": f"makespan {healthy[1].makespan_s * 1e6:.1f}us -> "
                  f"{healthy[4].makespan_s * 1e6:.1f}us "
                  f"({scaling:.3f}x)"})
    checks.append({
        "name": "fleet/single_replica_schedule_equals_zoo",
        "passed": ([_decision_key(d) for d in healthy[1].decisions]
                   == [_decision_key(d) for d in zoo_rep.decisions]),
        "detail": f"{len(healthy[1].decisions)} fleet vs "
                  f"{len(zoo_rep.decisions)} zoo decisions"})

    killed = {rid for rid, _ in CHAOS["kills"]}
    kill_t = dict(CHAOS["kills"])
    served_uids = {r.uid for r in chaos4.served}
    drained_served = [u for u in chaos4.drained_uids
                     if u in served_uids]
    checks.append({
        "name": "chaos/kill_observed_and_drain_to_peer_served",
        "passed": (any(e.kind == "kill" for e in chaos4.events)
                   and len(drained_served) >= 1),
        "detail": f"drained {list(chaos4.drained_uids)}, served after "
                  f"drain: {drained_served}"})
    late = [d for d in chaos4.decisions
            if d.replica in killed and d.t_s > kill_t[d.replica]]
    dead_states = [s.state for s in chaos4.per_replica
                   if s.replica in killed]
    checks.append({
        "name": "chaos/nothing_dispatches_on_dead_replica",
        "passed": not late and all(s == "dead" for s in dead_states),
        "detail": f"{len(late)} post-kill dispatches, final states "
                  f"{dead_states}"})
    shrunk = [p for p in chaos4.mesh_plans[1:]
              if p[1] < chaos4.mesh_plans[0][1]]
    checks.append({
        "name": "chaos/replan_proposes_shrunk_mesh_after_death",
        "passed": (any(e.kind == "replan" and "dead" in e.detail
                       for e in chaos4.events) and len(shrunk) >= 1),
        "detail": f"mesh plans {docs['chaos_r4']['mesh_plans']}"})
    kinds = {e.kind for e in chaos4.events}
    want = {"kill", "replica_dead", "drain", "suspect", "rejoin",
            "replan", "retry", "timeout"}
    checks.append({
        "name": "chaos/all_replica_fault_kinds_observed",
        "passed": want <= kinds,
        "detail": f"missing: {sorted(want - kinds)}"})
    checks.append({
        "name": "chaos/partition_suspect_then_rejoin",
        "passed": any(e.kind == "suspect" and e.replica == "r2"
                      for e in chaos4.events)
        and any(e.kind == "rejoin" and e.replica == "r2"
                for e in chaos4.events),
        "detail": "r2 suspected during its partition window and "
                  "rejoined after it healed"})
    checks.append({
        "name": "chaos/fleet_survives_serving_everything",
        "passed": (len(chaos4.served) == len(trace)
                   and chaos4.retry_count > 0),
        "detail": f"{len(chaos4.served)}/{len(trace)} served with "
                  f"{chaos4.retry_count} retries"})
    checks.append({
        "name": "determinism/modeled_schedule_replay_identical",
        "passed": _report_doc(replay) == docs["chaos_r4"],
        "detail": "same trace + chaos plan -> identical decisions, "
                  "events, statuses"})

    # -- the cooperative sharded-wave section ---------------------------
    sharded_models = {}
    for m in _models():
        full_b = SHARD_DATA * m.microbatch
        curve = {b: m.sharded_wave_cost(b, SHARD_DATA).speedup
                 for b in range(1, full_b + 1)}
        be = next((b for b, s in curve.items() if s >= 1.0), None)
        co = next((b for b, s in curve.items()
                   if s >= SHARD_THRESHOLD), None)
        full = m.sharded_wave_cost(full_b, SHARD_DATA)
        sharded_models[m.name] = {
            "microbatch": m.microbatch,
            "break_even_batch": be,
            "crossover_batch": co,
            "speedup_at_crossover": (round(curve[co], 4)
                                     if co is not None else None),
            "speedup_full_wave": round(curve[full_b], 4),
            "amortization_full_wave": round(full.amortization, 4),
            "broadcast_us": round(full.broadcast_s * 1e6, 3),
            "weight_stream_mib":
                round(full.weight_stream_bytes / 2**20, 3),
            "speedup_by_batch": {str(b): round(s, 4)
                                 for b, s in curve.items()},
        }
    checks.append({
        "name": "sharded/modeled_break_even_one_past_full_microbatch",
        "passed": all(v["break_even_batch"] == v["microbatch"] + 1
                      for v in sharded_models.values()),
        "detail": f"break-even batches "
                  f"{ {k: v['break_even_batch'] for k, v in sharded_models.items()} }"
                  f" vs microbatch {MAX_BATCH} (the shard trigger)"})
    checks.append({
        "name": "sharded/modeled_speedup_at_crossover_at_least_1p5x",
        "passed": all(v["crossover_batch"] is not None
                      and v["speedup_at_crossover"] >= SHARD_THRESHOLD
                      for v in sharded_models.values()),
        "detail": f"{ {k: (v['crossover_batch'], v['speedup_at_crossover']) for k, v in sharded_models.items()} }"})
    coop = [d for d in sharded4.decisions if d.shards]
    checks.append({
        "name": "sharded/cooperative_waves_formed_at_full_mesh",
        "passed": (len(coop) >= 1
                   and any(len(d.shards) == 4 and d.batch > MAX_BATCH
                           for d in coop)),
        "detail": f"{len(coop)} cooperative waves, batches "
                  f"{[d.batch for d in coop]}, widest mesh "
                  f"{max((len(d.shards) for d in coop), default=0)}"})
    checks.append({
        "name": "determinism/sharded_schedule_replay_identical",
        "passed": _report_doc(sharded_replay) == docs["sharded_r4"],
        "detail": "same trace + shard_waves -> identical cooperative "
                  "decisions, events, statuses"})
    sck = {e.kind for e in shard_chaos4.events}
    checks.append({
        "name": "sharded_chaos/midwave_kill_abort_reshard_retry_served",
        "passed": ({"shard_abort", "reshard", "kill", "retry"} <= sck
                   and len(shard_chaos4.served) == len(trace)),
        "detail": f"event kinds {sorted(sck)}; "
                  f"{len(shard_chaos4.served)}/{len(trace)} served "
                  f"after the mid-wave kill"})

    if EXECUTE:
        refs = served_refs(chaos4)
        bad = [r.uid for r in chaos4.served
               if not np.array_equal(np.asarray(r.logits), refs[r.uid])]
        checks.append({
            "name": "parity/served_logits_bitwise_equal_single_device",
            "passed": not bad,
            "detail": f"{len(chaos4.served)} served across "
                      f"{sum(s.served > 0 for s in chaos4.per_replica)}"
                      f" replicas, mismatched uids: {bad[:8]}"})
        nonfinite = [r.uid for r in chaos4.served
                     if not np.isfinite(np.asarray(r.logits)).all()]
        checks.append({
            "name": "guard/no_served_request_carries_nonfinite_logits",
            "passed": not nonfinite,
            "detail": f"non-finite uids: {nonfinite[:8]}"})
        # the tentpole invariant: a cooperative wave sharded over
        # data=4 serves every row bitwise-equal to the single-device
        # unbatched forward — with and without a mid-wave replica kill
        for name, rep in (("sharded_r4", sharded4),
                          ("sharded_chaos_r4", shard_chaos4)):
            srefs = served_refs(rep)
            sbad = [r.uid for r in rep.served
                    if not np.array_equal(np.asarray(r.logits),
                                          srefs[r.uid])]
            checks.append({
                "name": f"parity/{name}_logits_bitwise_equal_"
                        "single_device",
                "passed": not sbad,
                "detail": f"{len(rep.served)} served "
                          f"({sum(1 for d in rep.decisions if d.shards)}"
                          " cooperative waves), mismatched uids: "
                          f"{sbad[:8]}"})

    headline = {
        "n_requests": len(trace),
        "healthy_makespan_us": {
            str(nr): docs[f"healthy_r{nr}"]["makespan_us"]
            for nr in (1, 2, 4)},
        "healthy_scaling_1_to_4": round(scaling, 4),
        "round_robin_r4_makespan_us":
            docs["round_robin_r4"]["makespan_us"],
        "chaos_served": len(chaos4.served),
        "chaos_quarantined": len(chaos4.quarantined),
        "chaos_retry_count": chaos4.retry_count,
        "chaos_drained": len(chaos4.drained_uids),
        "chaos_makespan_us": docs["chaos_r4"]["makespan_us"],
        "sharded_break_even_batch": {
            k: v["break_even_batch"] for k, v in sharded_models.items()},
        "sharded_crossover_batch": {
            k: v["crossover_batch"] for k, v in sharded_models.items()},
        "sharded_speedup_at_crossover": {
            k: v["speedup_at_crossover"]
            for k, v in sharded_models.items()},
        "sharded_amortization_full_wave": {
            k: v["amortization_full_wave"]
            for k, v in sharded_models.items()},
        "sharded_cooperative_waves": len(coop),
        "sharded_makespan_us": docs["sharded_r4"]["makespan_us"],
        "sharded_chaos_makespan_us":
            docs["sharded_chaos_r4"]["makespan_us"],
    }

    import jax
    results = {"bench": "fleet_serve", "tier": tier,
               "backend": "pallas-interpret-cpu",
               "fleet": FLEET | {"replicas": [1, 2, 4],
                                 "placement": "least-loaded",
                                 "policy": "fifo"},
               "chaos": CHAOS | {
                   "stall_factors": list(CHAOS["stall_factors"]),
                   "kills": [list(k) for k in CHAOS["kills"]],
                   "partitions": [list(p) for p in CHAOS["partitions"]]},
               "recovery": RECOVERY,
               "sharded": {
                   "data": SHARD_DATA, "threshold": SHARD_THRESHOLD,
                   "chaos": {"kills": [list(k)
                                       for k in SHARD_CHAOS["kills"]]},
                   "models": sharded_models,
               },
               "trace": {
                   "seed": TRACE_TIERS[tier]["seed"],
                   "n_requests": len(trace),
                   "tenants": [{"tenant": t, "model": m, "n": n,
                                "rate_hz": r}
                               for t, m, n, r in
                               TRACE_TIERS[tier]["tenants"]],
               },
               "configs": docs,
               "headline": headline,
               "wall": {"executed": EXECUTE,
                        "devices": len(jax.devices()),
                        "platform": jax.devices()[0].platform,
                        "total_serve_s": round(wall_s, 3)},
               "checks": checks}
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(results, fh, indent=2)

    rows: list[Row] = [
        ("fleet_serve/healthy_scaling", 0.0,
         f"1->4 replicas {headline['healthy_scaling_1_to_4']:.3f}x "
         f"({headline['healthy_makespan_us']['1']:.0f}us -> "
         f"{headline['healthy_makespan_us']['4']:.0f}us)"),
        ("fleet_serve/chaos_r4", 0.0,
         f"{headline['chaos_served']} served / "
         f"{headline['chaos_quarantined']} quarantined of "
         f"{headline['n_requests']} with 1 dead replica, "
         f"{headline['chaos_drained']} drained, "
         f"{headline['chaos_retry_count']} retries"),
        ("fleet_serve/sharded_waves", 0.0,
         f"{headline['sharded_cooperative_waves']} cooperative waves, "
         "modeled crossover b="
         f"{headline['sharded_crossover_batch']['alexnet']} at "
         f"{headline['sharded_speedup_at_crossover']['alexnet']:.2f}x "
         "(alexnet, data=4)"),
        ("fleet_serve/json", 0.0,
         f"wrote {out_path} ({len(checks)} checks, "
         f"{sum(not c['passed'] for c in checks)} failed)"),
    ]
    raise_on_failed_checks(checks)
    return rows


def bench_rows() -> list[Row]:
    """run.py group entry: fast tier, writes BENCH_sharded.json."""
    return emit("BENCH_sharded.json", tier="fast")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_sharded.json")
    tier = ap.add_mutually_exclusive_group()
    tier.add_argument("--fast", dest="tier", action="store_const",
                      const="fast", default="fast",
                      help="CI smoke: ~22-request compute-bound trace")
    tier.add_argument("--full", dest="tier", action="store_const",
                      const="full",
                      help="nightly: ~36-request compute-bound trace")
    args = ap.parse_args()
    run_emit_cli(emit, args.out, args.tier)


if __name__ == "__main__":
    main()
