"""The serving plane: one scheduler, one executor and one integrity guard
for a model zoo on one chip or replicated over several.

MPNA's thesis is that many parallel arrays plus the right dataflow beat
one big array; this module carries it to serving.  :class:`ModelZooServer`
holds the zoo's compiled variants (:class:`~repro.serve.zoo.ZooModel`)
on ``n_replicas`` **replicas** — each a full dual-array pipeline holding
every model — and drains the admitted request stream through them.  A
zoo on one chip is the one-replica case; ``FleetServer`` names the same
class.

``serve()`` first runs a deterministic **modeled-time** schedule, then
executes every scheduled wave on the real kernels:

* **Admission** at ``max(arrival, earliest conv_free of a usable
  replica)``: bounded per-tenant queues, predictive shedding (or an int8
  reroute) priced by the scheduler's own cost model, and routing away
  from a *failed* variant to its int8 sibling.  A
  :class:`PlacementPolicy` puts each admitted, retried or drained request
  on a replica's queue.
* **The loop**: whenever a replica's SA-CONV array frees, the zoo's
  :class:`~repro.serve.zoo.SchedulingPolicy` picks which model's wave it
  dispatches; wave *i*'s SA-FC stage overlaps wave *i+1*'s SA-CONV stage
  (:func:`~repro.core.perf_model.zoo_wave_cost` prices both).  With
  ``shard_waves=True`` a model's backlog past one micro-batch becomes ONE
  cooperative wave of up to ``data x bb`` rows over every free usable
  replica (:func:`~repro.core.perf_model.sharded_wave_cost`).
* **Faults** come through one interface
  (:class:`~repro.serve.faults.FaultPlane`): a verdict per wave attempt,
  replica deaths and heartbeat partitions.  A verdict that blames the
  model (stall, corrupt rows, failed dispatch) feeds that model's
  :class:`~repro.serve.zoo.ModelHealth` and straggler monitor; one that
  blames the replica feeds only retry and the replica's straggler
  monitor.  Failed attempts retry with capped backoff and quarantine past
  the budget as typed errors.  A dead replica's queue drains to its
  peers, its in-flight wave retries elsewhere (a cooperative wave aborts
  and re-shards its rows over the survivors,
  :func:`~repro.distributed.elastic.reshard_wave`), and
  :func:`~repro.distributed.elastic.replan` logs the shrunk mesh; a
  partitioned replica is suspected, drained, and rejoins when its
  heartbeats return.
* **Execution**: each wave runs on its replica's lane, a
  :class:`~repro.serve.cnn_server.CNNServer` per (replica, model).  On
  the device that holds ``ZooModel.params`` the lane is
  ``ZooModel.server`` itself; other devices get a copy committed to
  them.  :meth:`ModelZooServer._guard` delivers every executed row
  through the host-side integrity guard
  (:func:`~repro.serve.errors.all_finite`).

Invariants: every admitted request ends as exactly one of served / shed /
quarantined; a served request's logits are **bitwise equal** to its
serving model's single-device unbatched forward, whichever replica,
retry or cooperative wave served it; the modeled schedule is a pure
function of (trace, configs, chaos plan) and never reads the device
count.

``serve()`` marks its host work with :mod:`repro.serve.telemetry` spans
(ident = the call's index): ``zoo.serve`` holding ``zoo.schedule``,
``zoo.execute`` (each wave's ``cnn.wave`` and one ``zoo.guard`` per
executed attempt, counting ``zoo.guard_rows`` checked and
``zoo.guard_rejects`` refused; a cooperative wave is
``fleet.sharded_wave``) and ``zoo.account``.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
from collections.abc import Callable, Sequence

import numpy as np

from repro.core.perf_model import WaveCost
from repro.core.schedule import ScheduleRegistry
from repro.distributed.elastic import replan, reshard_wave
from repro.distributed.fault_tolerance import HeartbeatTracker, StepMonitor
from repro.serve import telemetry
from repro.serve.cnn_server import CNNRequest, CNNServer
from repro.serve.errors import (CorruptOutputError, InsufficientReplicasError,
                                ReplicaLostError, RequestShedError,
                                ServeError, StaleDeadlineError,
                                WaveTimeoutError, all_finite)
from repro.serve.faults import FaultInjector, FaultPlane
from repro.serve.zoo import (AdmissionConfig, FaultEvent, FIFOPolicy,
                             ModelHealth, RecoveryConfig, ReplicaStats,
                             SchedulingPolicy, TenantStats, WaveAttempt,
                             WaveDecision, ZooModel, ZooReport, ZooRequest)

__all__ = ["PlacementPolicy", "LeastLoadedPlacement", "RoundRobinPlacement",
           "PLACEMENTS", "ReplicaView", "ModelZooServer", "FleetServer"]

#: The mesh :func:`~repro.distributed.elastic.replan` proposes after a
#: replica transition: weights unsharded, and the data degree dividing
#: this global batch within a pod of this many chips.
MESH_MODEL_PARALLEL = 1
MESH_GLOBAL_BATCH = 64
MESH_POD_SIZE = 64

#: Executor-side request uids, unique in the process: a model's own
#: server is the lane of every plane that serves the model, and a server
#: refuses a uid it has seen.
_EXEC_UIDS = itertools.count()


# ---------------------------------------------------------------------------
# placement: which replica absorbs an admitted (or drained) request
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ReplicaView:
    """What a placement policy may see of one candidate replica: its id,
    stable index, queued request count, modeled backlog (queued waves
    priced by the cost model) and how far its conv array is committed
    past ``now``.  A read-only projection — policies never touch the
    scheduler's state."""
    rid: str
    index: int
    queued: int
    backlog_s: float
    busy_s: float


class PlacementPolicy:
    """Picks the replica an admitted/drained/retried request lands on.
    ``place`` sees the candidate :class:`ReplicaView` list (sorted by
    replica index; only live, non-suspect replicas unless none exist;
    never fewer than two — a lone candidate needs no choice) and must
    return one of their ``rid``s, deterministically."""

    name = "base"

    def place(self, now: float, candidates: Sequence[ReplicaView],
              req: ZooRequest) -> str:
        raise NotImplementedError


class LeastLoadedPlacement(PlacementPolicy):
    """Cheapest-backlog replica first: modeled queued work plus residual
    array occupancy, ties broken by queue depth then replica index —
    the fleet twin of :class:`~repro.serve.zoo.ShortestMakespanPolicy`,
    with the same cost model as the oracle."""

    name = "least-loaded"

    def place(self, now, candidates, req):
        best = min(candidates,
                   key=lambda v: (v.backlog_s + v.busy_s, v.queued, v.index))
        return best.rid


class RoundRobinPlacement(PlacementPolicy):
    """Strict rotation over the candidate replicas — the baseline the
    load-aware policy is compared against.  The rotation counter only
    advances on a placement it is asked to decide (two or more
    candidates), so the assignment sequence is deterministic for a given
    trace."""

    name = "round-robin"

    def __init__(self) -> None:
        self._turn = 0

    def place(self, now, candidates, req):
        pick = candidates[self._turn % len(candidates)]
        self._turn += 1
        return pick.rid


PLACEMENTS: dict[str, Callable[[], PlacementPolicy]] = {
    "least-loaded": LeastLoadedPlacement,
    "round-robin": RoundRobinPlacement,
}


@dataclasses.dataclass
class _ReplicaState:
    """One replica's modeled pipeline during a drain (scheduler-internal)."""
    rid: str
    index: int
    alive: bool = True
    suspect: bool = False
    conv_free: float = 0.0
    fc_free: float = 0.0
    busy_s: float = 0.0
    waves: int = 0
    drained_away: int = 0
    pending: dict[str, list[ZooRequest]] = dataclasses.field(
        default_factory=dict)

    def usable(self) -> bool:
        return self.alive and not self.suspect

    def pending_n(self) -> int:
        return sum(len(q) for q in self.pending.values())

    @property
    def state_name(self) -> str:
        if not self.alive:
            return "dead"
        return "suspect" if self.suspect else "alive"


class ModelZooServer:
    """Hold several compiled models on ``n_replicas`` replicas, admit a
    mixed tagged request stream into per-tenant queues, and schedule
    dual-array waves with a pluggable policy priced by the planner's own
    cost model (see the module docstring).

    ``faults`` plugs in a seeded :class:`~repro.serve.faults.FaultPlane`
    (wave chaos or replica chaos); ``admission``/``recovery`` configure
    shedding, retry, health and degraded-mode policy.  With
    ``faults=None`` and the default configs the schedule is the healthy
    path.  ``devices`` (default ``jax.devices()``) are what replica lanes
    round-robin over; the modeled schedule never reads them."""

    def __init__(self, models: Sequence[ZooModel], *,
                 n_replicas: int = 1,
                 policy: SchedulingPolicy | None = None,
                 placement: PlacementPolicy | None = None,
                 registry: ScheduleRegistry | None = None,
                 faults: FaultPlane | None = None,
                 admission: AdmissionConfig | None = None,
                 recovery: RecoveryConfig | None = None,
                 devices: Sequence | None = None,
                 shard_waves: bool = False) -> None:
        if not models:
            raise ValueError("a zoo needs at least one model")
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        self.n_replicas = n_replicas
        self.replica_ids = tuple(f"r{i}" for i in range(n_replicas))
        self.policy = policy if policy is not None else FIFOPolicy()
        self.placement = placement if placement is not None \
            else LeastLoadedPlacement()
        self.faults = faults
        self.admission = admission if admission is not None \
            else AdmissionConfig()
        self.recovery = recovery if recovery is not None \
            else RecoveryConfig()
        self.shard_waves = shard_waves
        # the compiled-schedule registry: one (net, dtype, batch) entry
        # per model variant at its steady-state wave size
        self.registry = registry if registry is not None \
            else ScheduleRegistry()
        self.models: dict[str, ZooModel] = {}
        self._fallbacks: dict[str, str | None] = {}
        for m in models:
            self.add_model(m)
        self._devices = tuple(devices) if devices is not None else None
        self._lanes: dict[str, dict[str, CNNServer]] = {}
        self.tenants: dict[str, list[ZooRequest]] = {}
        self._rejected: list[ZooRequest] = []
        self._uids: set = set()
        self._attempt_idx = 0
        self._serve_calls = 0      # the telemetry ident of serve() calls

    def add_model(self, m: ZooModel) -> None:
        """Register one more compiled variant (elastic scale-up — valid
        between drains too).  Registers its stage schedules and refreshes
        the degraded-fallback routing table."""
        if m.name in self.models:
            raise ValueError(f"duplicate zoo model {m.name!r}")
        self.models[m.name] = m
        srv = m.server
        self.registry.register(
            m.spec.net, dtype_tag=m.spec.weight_dtype,
            batch=srv.microbatch, in_res=srv.in_res, in_ch=srv.in_ch,
            width_mult=srv.width_mult, dtype=srv.dtype,
            policy=srv.engine.policy, params=srv.params)
        # degraded-mode routing: fp32 variant -> int8 sibling of the SAME
        # net at the SAME serving resolution (images are interchangeable)
        self._fallbacks = {
            name: next((cand for cand, c in self.models.items()
                        if cand != name and zm.spec.weight_dtype != "int8"
                        and c.spec.net == zm.spec.net
                        and c.spec.weight_dtype == "int8"
                        and c.server.in_res == zm.server.in_res), None)
            for name, zm in self.models.items()}

    # -- devices / execution lanes (never consulted by the scheduler) -------
    def devices(self) -> tuple:
        """The JAX devices replica lanes round-robin over.  Lazy: the
        modeled schedule never needs them, so modeled-only drains never
        touch jax."""
        if self._devices is None:
            import jax
            self._devices = tuple(jax.devices())
        return self._devices

    def replica_device(self, index: int):
        devs = self.devices()
        return devs[index % len(devs)]

    def mesh(self):
        """A ``jax.sharding.Mesh`` over the replicas' **distinct** devices
        on one ``"data"`` axis — the mesh
        :func:`~repro.distributed.elastic.replan` proposals shrink."""
        return self.shard_mesh(self.replica_ids)

    def shard_mesh(self, rids: Sequence[str]):
        """The ``("data",)`` mesh a cooperative wave executes over: the
        **distinct** devices of the given replicas.  With fewer host
        devices than replicas the mesh is narrower than the logical
        ``data`` degree; the modeled schedule never reads it."""
        from jax.sharding import Mesh
        distinct = []
        for rid in rids:
            d = self.replica_device(self.replica_ids.index(rid))
            if d not in distinct:
                distinct.append(d)
        return Mesh(np.array(distinct), axis_names=("data",))

    def _lane(self, rid: str, model: str) -> CNNServer:
        """The replica's execution lane for one model: ``ZooModel.server``
        on the device that holds the model's parameters, elsewhere a
        ``CNNServer`` whose parameters are committed to the replica's own
        device, so a wave never copies weights between devices."""
        import jax

        lane = self._lanes.setdefault(rid, {})
        srv = lane.get(model)
        if srv is None:
            m = self.models[model]
            device = self.replica_device(self.replica_ids.index(rid))
            leaf = jax.tree.leaves(m.params)[0]
            home = next(iter(leaf.devices())) \
                if isinstance(leaf, jax.Array) else jax.devices()[0]
            srv = lane[model] = m.server if device == home else CNNServer(
                m.spec.net, jax.device_put(m.params, device),
                in_res=m.server.in_res, width_mult=m.server.width_mult,
                max_batch=m.server.max_batch)
        return srv

    def _mesh_params(self, model: str, rids: Sequence[str], mesh):
        """``model``'s parameters replicated over ``mesh``, assembled from
        the participant lanes' own committed copies: no second copy of
        the weights is made on any device."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        on_device = {}
        for rid in rids:
            d = self.replica_device(self.replica_ids.index(rid))
            on_device.setdefault(d, self._lane(rid, model).params)
        sharding = NamedSharding(mesh, PartitionSpec())
        return jax.tree.map(
            lambda *leaves: jax.make_array_from_single_device_arrays(
                leaves[0].shape, sharding, list(leaves)),
            *(on_device[d] for d in mesh.devices.flat))

    # -- admission ----------------------------------------------------------
    def submit(self, req: ZooRequest) -> bool:
        """Admit one tagged request into its tenant's queue; returns
        ``True`` if queued.  Unknown model names and duplicate uids raise
        (caller bugs, the registry's lookup contract).  A deadline
        already in the past at arrival is a *policy* rejection: the
        request is shed immediately with a typed
        :class:`~repro.serve.errors.StaleDeadlineError` result (it still
        appears, accounted, in the next report) and ``False`` returns."""
        if req.model not in self.models:
            raise KeyError(f"unknown zoo model {req.model!r}; "
                           f"serving: {tuple(self.models)}")
        if req.uid in self._uids:
            raise ValueError(f"duplicate request uid {req.uid}: uids are "
                             "unique per zoo lifetime")
        self._uids.add(req.uid)
        if req.deadline_s is not None and req.deadline_s <= req.arrival_s:
            req.status, req.error = "shed", StaleDeadlineError(
                f"deadline {req.deadline_s:.6f}s already past at arrival "
                f"{req.arrival_s:.6f}s", uid=req.uid, model=req.model)
            self._rejected.append(req)
            return False
        self.tenants.setdefault(req.tenant, []).append(req)
        return True

    def pending_count(self) -> int:
        return sum(len(q) for q in self.tenants.values())

    # -- modeled cost helpers ------------------------------------------------
    def _cost(self, model: str, queued: int) -> WaveCost:
        m = self.models[model]
        return m.wave_cost(min(queued, m.microbatch))

    def _backlog_s(self, st: _ReplicaState) -> float:
        total = 0.0
        for model, q in st.pending.items():
            if not q:
                continue
            mb = self.models[model].microbatch
            waves = -(-len(q) // mb)
            total += waves * self._cost(model, min(len(q), mb)).total_s
        return total

    def _views(self, now: float, states: list[_ReplicaState]
               ) -> list[ReplicaView]:
        return [ReplicaView(st.rid, st.index, st.pending_n(),
                            self._backlog_s(st),
                            max(0.0, st.conv_free - now))
                for st in states]

    def _route(self, req: ZooRequest,
               health: dict[str, ModelHealth]) -> tuple[str, str | None]:
        """Health-based routing: a request for a *failed* variant drains
        to its int8 sibling when eligible.  Returns (route, reason)."""
        primary = req.model
        if health[primary].state != "failed":
            return primary, None
        alt = self._fallbacks.get(primary)
        if (alt is not None and self.recovery.allow_degraded
                and req.allow_degraded
                and health[alt].state != "failed"):
            return alt, f"{primary} failed -> int8 fallback {alt}"
        return primary, None

    def _backoff(self, retries: int) -> float:
        rec = self.recovery
        return min(rec.backoff_cap_s,
                   rec.backoff_s * rec.backoff_mult ** (retries - 1))

    # -- scheduling (deterministic modeled time, device-count independent) --
    def _schedule(self, requests: list[ZooRequest]):
        """The modeled-time simulation: admit, place, pick waves whenever
        a replica's SA-CONV array frees, consult the fault plane once per
        wave attempt, and drive retry / quarantine / health / drain /
        degradation off the outcomes.  Stamps every request's terminal
        status; a pure function of the request list, the configs and the
        chaos plan."""
        adm, rec, inj = self.admission, self.recovery, self.faults
        undisp = sorted(requests, key=lambda r: (r.arrival_s, r.uid))
        states: dict[str, _ReplicaState] = {
            rid: _ReplicaState(rid, idx,
                               pending={m: [] for m in self.models})
            for idx, rid in enumerate(self.replica_ids)}
        tenant_depth: dict[str, int] = {}
        resharded: dict[int, str] = {}   # uid -> survivor pinned by reshard
        retry_heap: list[tuple[float, int, ZooRequest]] = []
        decisions: list[WaveDecision] = []
        attempts: list[WaveAttempt] = []
        events: list[FaultEvent] = []
        health = {m: ModelHealth(m) for m in self.models}

        monitors: dict[str, StepMonitor] = {}   # by the model or replica

        def straggler(blamed: str, attempt: int, stall: float) -> bool:
            """Feed the blamed model's or replica's straggler monitor."""
            mon = monitors.get(blamed)
            if mon is None:
                mon = monitors[blamed] = StepMonitor(
                    factor=rec.straggler_factor,
                    warmup=rec.straggler_warmup,
                    window=rec.straggler_window)
            return mon.observe(attempt, stall) == "straggler"
        # a model with pending work whose waves stopped completing times
        # out, where the fault plane can fail models at all
        watch_models = inj is not None and inj.blames == "model"
        model_beats = HeartbeatTracker(list(self.models),
                                       timeout=rec.heartbeat_timeout_s,
                                       now=0.0)
        replica_beats = HeartbeatTracker(list(self.replica_ids),
                                         timeout=rec.heartbeat_timeout_s,
                                         now=0.0)
        kills: dict[str, float] = {}
        partitions: list[tuple[str, float, float]] = []
        if inj is not None:
            for rid in self.replica_ids:
                t_kill = inj.kill_time(rid)
                if t_kill is not None:
                    kills[rid] = t_kill
                partitions.extend((rid, s, e)
                                  for s, e in inj.partition_windows(rid))
        part_done = [False] * len(partitions)
        mesh_plans = [(0.0, replan(
            self.n_replicas, model_parallel=MESH_MODEL_PARALLEL,
            global_batch=MESH_GLOBAL_BATCH, pod_size=MESH_POD_SIZE).data,
            0, "initial")]
        now = 0.0
        i, n = 0, len(undisp)
        terminal = 0
        seq = 0                          # retry-heap tiebreak

        def partitioned(rid: str, t: float) -> bool:
            return inj is not None and inj.partitioned(rid, t)

        def health_event(t: float, model: str, new: str | None,
                         why: str) -> None:
            if new is not None:
                events.append(FaultEvent(t, -1, model, "health",
                                         f"-> {new} ({why})"))

        def place(r: ZooRequest, route: str, t: float) -> str | None:
            """Queue ``r`` for variant ``route`` on a replica; None =
            nowhere left.  A request whose cooperative wave was aborted
            mid-flight carries a :func:`~repro.distributed.elastic
            .reshard_wave` pin — honor it while that survivor is usable;
            free placement is the fallback."""
            pinned = resharded.pop(r.uid, None)
            if pinned is not None and states[pinned].usable():
                st = states[pinned]
            else:
                # every live replica suspect: a drained fleet beats a
                # wedged one — fall back to suspects rather than dropping
                cands = [s for s in states.values() if s.usable()] \
                    or [s for s in states.values() if s.alive]
                if len(cands) > 1:
                    cands = [states[self.placement.place(
                        t, self._views(t, cands), r)]]
                if not cands:
                    return None
                st = cands[0]
            r.replica, r.served_by = st.rid, route
            st.pending[route].append(r)
            tenant_depth[r.tenant] = tenant_depth.get(r.tenant, 0) + 1
            return st.rid

        def shed(r: ZooRequest, t: float, why: str, detail: str) -> None:
            nonlocal terminal
            r.status, r.error = "shed", RequestShedError(
                detail, uid=r.uid, model=r.model)
            events.append(FaultEvent(t, -1, r.model, "shed", why,
                                     uids=(r.uid,)))
            terminal += 1

        def quarantine_lost(r: ZooRequest, t: float, why: str) -> None:
            nonlocal terminal
            r.status, r.error = "quarantined", ReplicaLostError(
                why, uid=r.uid, model=r.model, replica=r.replica or "")
            events.append(FaultEvent(t, -1, r.model, "quarantine", why,
                                     uids=(r.uid,),
                                     replica=r.replica or "-"))
            terminal += 1

        def do_replan(t: float, why: str) -> None:
            alive = sum(st.usable() for st in states.values())
            try:
                plan = replan(alive, model_parallel=MESH_MODEL_PARALLEL,
                              global_batch=MESH_GLOBAL_BATCH,
                              pod_size=MESH_POD_SIZE)
            except InsufficientReplicasError as e:
                events.append(FaultEvent(t, -1, "", "replan_failed",
                                         f"{why}: {e.message}"))
                return
            mesh_plans.append((t, plan.data, plan.wasted_chips, why))
            events.append(FaultEvent(
                t, -1, "", "replan",
                f"{why}: {alive} usable -> data={plan.data} "
                f"wasted={plan.wasted_chips}"))

        def drain_queue(st: _ReplicaState, t: float, why: str) -> None:
            """Move every queued request off ``st`` to surviving peers
            (or quarantine when none remain)."""
            for model in st.pending:
                moved, st.pending[model] = st.pending[model], []
                for r in moved:
                    tenant_depth[r.tenant] -= 1
                    st.drained_away += 1
                    new_rid = place(r, model, t)
                    if new_rid is None:
                        quarantine_lost(
                            r, t, f"{why}; no surviving replica to "
                            "drain to")
                    else:
                        events.append(FaultEvent(
                            t, -1, model, "drain",
                            f"{why}: queued request -> {new_rid}",
                            uids=(r.uid,), replica=st.rid))

        def fire_kill(rid: str, t: float) -> None:
            st = states[rid]
            del kills[rid]
            st.alive = st.suspect = False
            events.append(FaultEvent(t, -1, "", "kill", "replica died",
                                     replica=rid))
            replica_beats.deregister(rid)   # stop tripping liveness forever
            drain_queue(st, t, f"replica {rid} died")
            do_replan(t, f"{rid} dead")

        def fail_wave(wave: list[ZooRequest], rid: str, model: str,
                      t: float, kind: str, attempt: int) -> None:
            """Retry-or-quarantine every request of a failed attempt."""
            nonlocal terminal, seq
            for r in wave:
                r.retries += 1
                if r.retries > rec.max_retries:
                    err_cls = {"timeout": WaveTimeoutError,
                               "corrupt": CorruptOutputError,
                               "replica_dead": ReplicaLostError}.get(
                                   kind, ServeError)
                    kw = {"replica": rid} \
                        if err_cls is ReplicaLostError else {}
                    r.status, r.error = "quarantined", err_cls(
                        f"wave {kind} x{r.retries} attempts (retry "
                        f"budget {rec.max_retries} spent)", uid=r.uid,
                        model=model, **kw)
                    events.append(FaultEvent(
                        t, attempt, model, "quarantine",
                        f"{kind} after {r.retries} attempts",
                        uids=(r.uid,), replica=rid))
                    terminal += 1
                else:
                    delay = self._backoff(r.retries)
                    seq += 1
                    heapq.heappush(retry_heap, (t + delay, seq, r))
                    events.append(FaultEvent(
                        t, attempt, model, "retry",
                        f"{kind}; backoff {delay * 1e6:.0f}us",
                        uids=(r.uid,), replica=rid))

        def admit(r: ZooRequest, t: float) -> None:
            """Admission control at the request's admission instant."""
            route = r.model
            if adm.max_queue is not None \
                    and tenant_depth.get(r.tenant, 0) >= adm.max_queue:
                shed(r, t, f"queue full (tenant {r.tenant})",
                     f"tenant {r.tenant!r} queue full "
                     f"({adm.max_queue} pending)")
                return
            if r.deadline_s is not None and adm.predictive_shedding:
                # best case: dispatched immediately, solo wave — if even
                # that misses, scheduling it can only waste array time
                best = t + self.models[route].wave_cost(1).total_s
                if best > r.deadline_s:
                    alt = self._fallbacks.get(route)
                    if (alt is not None and rec.allow_degraded
                            and r.allow_degraded
                            and health[alt].state != "failed"
                            and t + self.models[alt].wave_cost(1).total_s
                            <= r.deadline_s):
                        events.append(FaultEvent(
                            t, -1, route, "degrade",
                            f"predicted miss on {route} -> {alt}",
                            uids=(r.uid,)))
                        route = alt
                    else:
                        shed(r, t, "predicted deadline miss",
                             f"cost model predicts deadline miss: "
                             f"best-case finish {best:.6f}s > deadline "
                             f"{r.deadline_s:.6f}s")
                        return
            if route == r.model:
                route, why = self._route(r, health)
                if why is not None:
                    events.append(FaultEvent(t, -1, r.model, "degrade",
                                             why, uids=(r.uid,)))
            if place(r, route, t) is None:
                quarantine_lost(r, t, "no surviving replica at admission")

        def admit_due(strict: bool) -> None:
            """Admit, at ``now``, the arrivals and then the retries due
            before it (``strict``) or at it."""
            nonlocal i
            while i < n and (undisp[i].arrival_s < now or not strict
                             and undisp[i].arrival_s == now):
                admit(undisp[i], now)
                i += 1
            while retry_heap and (retry_heap[0][0] < now or not strict
                                  and retry_heap[0][0] == now):
                _, _, r = heapq.heappop(retry_heap)
                route, why = self._route(r, health)
                if why is not None:
                    events.append(FaultEvent(now, -1, r.model, "degrade",
                                             why, uids=(r.uid,)))
                if place(r, route, now) is None:
                    quarantine_lost(r, now, "no surviving replica for retry")

        def usable_free() -> float:
            """The earliest instant a usable replica's conv array frees:
            requests are admitted no earlier (0 when none is usable)."""
            return min((st.conv_free for st in states.values()
                        if st.usable()), default=0.0)

        guard = 0
        max_iters = (128 + 16 * n * (rec.max_retries + 2)
                     + 64 * (len(kills) + len(partitions)))
        while terminal < n:
            guard += 1
            if guard > max_iters:          # never wedge, even on a bug
                raise ServeError(
                    f"scheduler exceeded {max_iters} iterations with "
                    f"{n - terminal} request(s) unresolved — scheduling "
                    "invariant broken")
            # -- next modeled instant anything can happen -------------------
            t_free = usable_free()
            nxt = [st.conv_free for st in states.values()
                   if st.usable() and st.pending_n()]
            if i < n:
                nxt.append(max(undisp[i].arrival_s, t_free))
            if retry_heap:
                nxt.append(max(retry_heap[0][0], t_free))
            nxt.extend(kills.values())
            for w, (rid, s, e) in enumerate(partitions):
                if part_done[w] or not states[rid].alive:
                    continue
                if e <= now:
                    part_done[w] = True
                    continue
                nxt.extend(t for t in (s, s + rec.heartbeat_timeout_s, e)
                           if t > now)
            if not nxt:
                # nothing can ever happen again: quarantine the rest
                # (defensive — the drain paths should already have)
                for _, _, r in sorted(retry_heap):
                    quarantine_lost(r, now,
                                    "idle with no live replica")
                retry_heap.clear()
                while i < n:
                    admit(undisp[i], max(now, undisp[i].arrival_s))
                    i += 1
                continue
            now = max(now, min(nxt))
            # -- admission happens at max(t, earliest usable conv_free),
            # but never after a replica transition: what is due before a
            # death is placed ahead of it, what is due at a suspect or a
            # rejoin ahead of that, as a placement on arrival would be ----
            dying = [rid for rid, t in kills.items() if t <= now]
            if dying:
                admit_due(strict=True)
            for rid in dying:
                fire_kill(rid, kills[rid])
            suspects, rejoins = [], []
            if partitions:     # only a dropped heartbeat makes a suspect
                for st in states.values():
                    if st.alive and not partitioned(st.rid, now):
                        replica_beats.beat(st.rid, now)
                failed_now = replica_beats.failed(now)
                suspects = [states[rid] for rid in failed_now
                            if states[rid].alive and not states[rid].suspect]
                rejoins = [st for st in states.values() if st.alive
                           and st.suspect and st.rid not in failed_now]
            if suspects or rejoins or usable_free() <= now:
                admit_due(strict=False)
            for st in suspects:
                st.suspect = True
                events.append(FaultEvent(
                    now, -1, "", "suspect",
                    f"no heartbeat for > "
                    f"{rec.heartbeat_timeout_s * 1e6:.0f}us "
                    "(partitioned?)", replica=st.rid))
                drain_queue(st, now, f"replica {st.rid} suspected")
                do_replan(now, f"{st.rid} suspect")
            for st in rejoins:
                st.suspect = False
                events.append(FaultEvent(
                    now, -1, "", "rejoin",
                    "heartbeats resumed; replica back in rotation",
                    replica=st.rid))
                do_replan(now, f"{st.rid} rejoined")
            if watch_models:
                for m in self.models:
                    if not any(st.pending[m] for st in states.values()):
                        model_beats.beat(m, now)
                for m in model_beats.failed(now):
                    health_event(now, m, health[m].force_failed(),
                                 "heartbeat timeout")
            # -- dispatch one wave ------------------------------------------
            ready = [st for st in states.values()
                     if st.usable() and st.pending_n()
                     and st.conv_free <= now]
            if not ready:
                continue
            st = min(ready, key=lambda s: (s.conv_free, s.index))
            rid = st.rid
            cands = {m: q for m, q in st.pending.items() if q}
            depths = tuple(sorted((m, len(q)) for m, q in cands.items()))
            chosen = self.policy.pick(now, cands, self._cost)
            zm = self.models[chosen]
            participants, wave = [st], None
            # Cooperative sharded wave: the free usable replicas' queue of
            # the chosen model past one planner micro-batch is the modeled
            # crossover trigger (perf_model.fleet_shard_crossover_batch
            # breaks even one row past a full micro-batch wave): cut ONE
            # wave of up to data x bb rows and run it across the mesh.
            # Below data=2 it falls back to the one-replica wave.
            if self.shard_waves:
                free = sorted((s for s in states.values()
                               if s.usable() and s.conv_free <= now),
                              key=lambda s: s.index)
                merged = self.policy.wave_order(
                    [r for p in free for r in p.pending[chosen]])
                if len(merged) > zm.microbatch and len(free) < 2:
                    events.append(FaultEvent(
                        now, -1, chosen, "shard_fallback",
                        f"mesh below data=2 ({len(free)} usable "
                        "replica(s) free); cooperative wave falls back "
                        "to the per-replica lane", replica=rid))
                elif len(merged) > zm.microbatch:
                    participants = free
                    wave = merged[:zm.sharded_microbatch(len(free))]
                    cut = {id(r) for r in wave}
                    for p in free:
                        p.pending[chosen] = [r for r in p.pending[chosen]
                                             if id(r) not in cut]
            if wave is None:
                queue = self.policy.wave_order(st.pending[chosen])
                wave, st.pending[chosen] = \
                    queue[:zm.microbatch], queue[zm.microbatch:]
            for r in wave:
                tenant_depth[r.tenant] -= 1
            shards = tuple(p.rid for p in participants) \
                if len(participants) > 1 else ()
            cost = zm.sharded_wave_cost(len(wave), len(shards)) \
                .as_wave_cost() if shards else zm.wave_cost(len(wave))
            attempt = self._attempt_idx
            self._attempt_idx += 1
            verdict = inj.verdict(st.index, attempt, len(wave)) \
                if inj is not None else None
            kind = verdict.kind if verdict is not None else "none"
            on_model = verdict is not None and verdict.blames == "model"
            uids = tuple(r.uid for r in wave)
            stall = verdict.stall_factor if kind == "stall" else 1.0
            timed_out = stall >= rec.wave_timeout_factor
            eff = cost.scaled(min(stall, rec.wave_timeout_factor)) \
                if stall != 1.0 else cost
            if kind == "dispatch":
                # a transient PlanError at dispatch: no array occupied
                eff = dataclasses.replace(eff, conv_s=0.0, fc_s=0.0)

            def decide(fault: str) -> None:
                decisions.append(WaveDecision(
                    index=len(decisions), t_s=now, model=chosen,
                    uids=uids, batch=len(wave), conv_s=eff.conv_s,
                    fc_s=eff.fc_s, queue_depths=depths, fault=fault,
                    stall_factor=stall, replica=rid, shards=shards))

            def lost(at: float, why: str) -> None:
                attempts.append(WaveAttempt(
                    attempt, chosen, list(wave), verdict, deliver=(),
                    execute=False, replica=rid, shards=shards))
                fail_wave(wave, rid, chosen, at, why, attempt)
                if on_model:
                    health_event(at, chosen,
                                 health[chosen].on_failure(rec), why)

            if kind == "dispatch":
                events.append(FaultEvent(now, attempt, chosen, "dispatch",
                                         "injected transient dispatch "
                                         "failure", uids, replica=rid))
                decide("dispatch")
                lost(now, "dispatch")
                continue
            conv_done = now + eff.conv_s
            fc_start = max(conv_done, max(p.fc_free for p in participants))
            fc_done = fc_start + eff.fc_s

            victims = sorted((kills[p.rid], p.rid) for p in participants
                             if p.rid in kills
                             and now < kills[p.rid] <= fc_done)
            if victims:
                # a replica dies mid-wave: the wave is lost with it (a
                # cooperative wave aborts whole and re-shards its rows
                # over the survivors), and its requests retry
                t_kill, dead = victims[0]
                events.append(FaultEvent(
                    t_kill, attempt, chosen,
                    "shard_abort" if shards else "replica_dead",
                    f"participant {dead} died mid-wave; cooperative "
                    f"data={len(shards)} wave aborted" if shards else
                    "replica died mid-wave; in-flight wave lost",
                    uids, replica=dead))
                decide("replica_dead")
                attempts.append(WaveAttempt(
                    attempt, chosen, list(wave), verdict, deliver=(),
                    execute=False, replica=rid, shards=shards))
                for p in participants:
                    p.waves += 1
                fire_kill(dead, t_kill)
                if shards:
                    survivors = [p.rid for p in participants
                                 if states[p.rid].usable()]
                    try:
                        asg = reshard_wave(uids, survivors)
                    except InsufficientReplicasError as e:
                        events.append(FaultEvent(
                            t_kill, attempt, chosen, "replan_failed",
                            f"reshard: {e.message}", uids))
                    else:
                        resharded.update({u: r for r, us in asg.assignment
                                          for u in us})
                        events.append(FaultEvent(
                            t_kill, attempt, chosen, "reshard",
                            "in-flight wave re-sharded over "
                            f"data={asg.data}: " + " ".join(
                                f"{r}x{len(us)}" for r, us in
                                asg.assignment), uids, replica=dead))
                fail_wave(wave, dead, chosen, t_kill, "replica_dead",
                          attempt)
                continue

            # one-deep stage buffer, like the pipelined CNNServer: the next
            # wave's conv stage may start only once this wave's features
            # have been handed to the SA-FC array
            for p in participants:
                p.conv_free, p.fc_free = max(conv_done, fc_start), fc_done
                p.busy_s += eff.total_s
                p.waves += 1

            if timed_out:
                # aborted at the timeout: the arrays were occupied that
                # long, but nothing completed — no heartbeat, all retry
                events.append(FaultEvent(
                    now, attempt, chosen, "timeout",
                    f"stall x{stall:g} >= timeout factor "
                    f"{rec.wave_timeout_factor:g}, "
                    f"{'sharded ' if shards else ''}wave aborted", uids,
                    replica=rid))
                decide("timeout")
                lost(fc_done, "timeout")
                continue

            # the wave completed (cleanly, late, or with corrupt rows)
            for p in participants:
                if not partitioned(p.rid, fc_done):
                    replica_beats.beat(p.rid, fc_done)
            model_beats.beat(chosen, fc_done)
            # a straggler verdict needs a fault plane to stall the wave
            if verdict is not None and straggler(
                    chosen if on_model else rid, attempt, stall):
                events.append(FaultEvent(
                    fc_done, attempt, chosen, "stall",
                    f"straggler verdict: x{stall:g} modeled "
                    f"{'sharded ' if shards else ''}wave time", uids,
                    replica=rid))
                if on_model:
                    health_event(fc_done, chosen,
                                 health[chosen].on_straggler(rec),
                                 "straggler")
            corrupt_rows = frozenset(verdict.corrupt_rows) \
                if kind == "corrupt" else frozenset()
            served = [r for j, r in enumerate(wave) if j not in corrupt_rows]
            failed = [r for j, r in enumerate(wave) if j in corrupt_rows]
            for r in served:
                r.dispatch_s, r.finish_s = now, fc_done
                r.status, r.replica = "served", rid
            terminal += len(served)
            decide(kind)
            attempts.append(WaveAttempt(
                attempt, chosen, list(wave), verdict,
                deliver=tuple(r.uid for r in served), replica=rid,
                shards=shards))
            if failed:
                events.append(FaultEvent(
                    fc_done, attempt, chosen, "corrupt",
                    f"non-finite logits in rows "
                    f"{tuple(sorted(corrupt_rows))}",
                    uids=tuple(r.uid for r in failed), replica=rid))
                fail_wave(failed, rid, chosen, fc_done, "corrupt", attempt)
                health_event(fc_done, chosen,
                             health[chosen].on_failure(rec), "corrupt")
            else:
                health_event(fc_done, chosen,
                             health[chosen].on_clean(rec), "clean wave")
        return decisions, attempts, events, health, states, mesh_plans

    # -- execution (real kernels, bitwise per-request logits) ---------------
    def _execute(self, attempts: list[WaveAttempt],
                 events: list[FaultEvent], call: int) -> None:
        """Run every completed attempt on its replica's lane, then deliver
        its rows through :meth:`_guard`.  Lanes get the host images and
        move each wave to their device in one transfer.  Where the next
        attempt to run goes to the same lane, the lane is told its rows
        (:meth:`CNNServer.prefetch`), so their upload runs under this
        wave's device work.  An executor exception quarantines the
        attempt's undelivered requests instead of wedging the drain.
        ``call`` is the ``serve()`` call's index, the ident of its
        spans."""
        def rows(a: WaveAttempt) -> list[CNNRequest]:
            return [CNNRequest(uid=next(_EXEC_UIDS), image=r.image)
                    for r in a.requests]

        runs = [a for a in attempts if a.execute]
        ahead: list[CNNRequest] | None = None
        for k, a in enumerate(runs):
            if a.shards:
                self._execute_sharded(a, events)
                continue
            srv = self._lane(a.replica, a.model)
            reqs = rows(a) if ahead is None else ahead
            exec_uids = [c.uid for c in reqs]
            for c in reqs:
                srv.submit(c)
            nxt = runs[k + 1] if k + 1 < len(runs) else None
            ahead = None
            if nxt is not None and not nxt.shards \
                    and (nxt.replica, nxt.model) == (a.replica, a.model):
                ahead = rows(nxt)
                srv.prefetch(ahead)
            try:
                completed = {c.uid: c for c in srv.step_wave()}
            except Exception as e:      # noqa: BLE001 — never wedge
                srv.cancel(exec_uids)
                self._lost(a, e, events)
                continue
            self._guard(a, [None if eu not in completed
                            else completed[eu].logits for eu in exec_uids],
                        events, call)

    def _lost(self, a: WaveAttempt, e: Exception,
              events: list[FaultEvent]) -> None:
        """Quarantine the requests an attempt would have delivered when
        its executor raised."""
        deliver = set(a.deliver)
        for r in a.requests:
            if r.uid in deliver:
                r.status, r.error = "quarantined", ServeError(
                    f"wave execution raised {type(e).__name__}: {e}",
                    uid=r.uid, model=a.model)
                events.append(FaultEvent(
                    -1.0, a.index, a.model, "quarantine",
                    f"executor raised {type(e).__name__}",
                    uids=(r.uid,), replica=a.replica))

    def _guard(self, a: WaveAttempt, rows: list, events: list[FaultEvent],
               call: int) -> None:
        """Deliver one executed attempt's rows (``None`` where the
        executor lost one) through the integrity guard:
        :func:`~repro.serve.errors.all_finite` on each row's host copy of
        its logits, with no device call.  Rows the chaos plane corrupts
        are overwritten first, at the flush boundary; the guard must
        agree with the modeled schedule, and also refuses *genuine*
        non-finite rows the schedule expected clean."""
        corrupt_rows = frozenset(a.faults.corrupt_rows) \
            if a.faults is not None and a.faults.kind == "corrupt" \
            else frozenset()
        deliver = set(a.deliver)
        with telemetry.span("zoo.guard", call):
            telemetry.count("zoo.guard_rows",
                            sum(row is not None for row in rows))
            for j, (r, logits) in enumerate(zip(a.requests, rows)):
                if logits is None:       # executor lost a row: typed, loud
                    if r.uid in deliver:
                        r.status, r.error = "quarantined", ServeError(
                            "executor returned no completion for the "
                            "request's wave row", uid=r.uid, model=a.model)
                        events.append(FaultEvent(
                            -1.0, a.index, a.model, "quarantine",
                            "executor lost a wave row", uids=(r.uid,),
                            replica=a.replica))
                    continue
                logits = np.asarray(logits)
                if j in corrupt_rows:
                    logits = FaultInjector.corrupt_array(logits)
                if not all_finite(logits):
                    telemetry.count("zoo.guard_rejects")
                    if r.uid in deliver:
                        r.status, r.error = "quarantined", \
                            CorruptOutputError(
                                "non-finite logits at the integrity guard",
                                uid=r.uid, model=a.model)
                        events.append(FaultEvent(
                            -1.0, a.index, a.model, "quarantine",
                            "integrity guard: genuine non-finite logits",
                            uids=(r.uid,), replica=a.replica))
                    continue
                if r.uid in deliver:
                    r.logits, r.done = logits, True

    def _execute_sharded(self, a: WaveAttempt,
                         events: list[FaultEvent]) -> None:
        """Run one cooperative wave over the participants' mesh: the
        row batch is committed to the ``("data",)`` axis with
        ``jax.device_put`` + ``NamedSharding``
        (:func:`~repro.distributed.sharding.shard_wave_rows`, which pads
        non-divisible batches with zero rows), the parameters are
        replicated on the mesh, and the model's forward runs under
        ``shard_map`` over ``("data",)``: each device runs the kernels on
        its own rows (a Pallas TPU kernel cannot be partitioned by the
        compiler).  Inside the map the forward runs the same compiled
        stage programs the per-replica lanes run, at the per-device row
        count; rows are independent in every kernel, and a whole stage
        under one ``jax.jit`` computes each row bitwise as the batch-1
        forward does (``tests/test_stage_program.py``), so each served
        row stays **bitwise equal** to the single-device unbatched
        forward.  Its host work is the span ``fleet.sharded_wave``."""
        # ident: the serve() call this wave belongs to
        call = self._serve_calls - 1
        with telemetry.span("fleet.sharded_wave", call):
            self._run_sharded(a, events, call)

    def _run_sharded(self, a: WaveAttempt, events: list[FaultEvent],
                     call: int) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        from repro.distributed.sharding import shard_wave_rows
        from repro.models import cnn

        m = self.models[a.model]
        try:
            mesh = self.shard_mesh(a.shards)
            x = jnp.stack([jnp.asarray(r.image, m.server.dtype)
                           for r in a.requests])
            xs, rows = shard_wave_rows(x, mesh)
            forward = jax.shard_map(
                lambda p, xv: cnn.cnn_forward(m.spec.net, p, xv,
                                              eng=m.server.engine),
                mesh=mesh, in_specs=(PartitionSpec(),
                                     PartitionSpec("data")),
                out_specs=PartitionSpec("data"), check_vma=False)
            params = self._mesh_params(a.model, a.shards, mesh)
            logits = np.asarray(forward(params, xs))[:rows]
        except Exception as e:          # noqa: BLE001 — never wedge
            self._lost(a, e, events)
            return
        self._guard(a, list(logits), events, call)

    # -- drain ---------------------------------------------------------------
    def serve(self, *, execute: bool = True) -> ZooReport:
        """Drain every per-tenant queue: schedule (modeled time), execute
        (real kernels; skipped with ``execute=False`` for modeled-only
        analysis — the schedule, statuses and accounting are
        execution-independent by construction), account.  Returns the
        :class:`~repro.serve.zoo.ZooReport`; the admitted requests are
        completed in place, each in exactly one terminal status."""
        call = self._serve_calls
        self._serve_calls += 1
        with telemetry.span("zoo.serve", call):
            return self._serve(execute, call)

    def _serve(self, execute: bool, call: int) -> ZooReport:
        queued = [r for q in self.tenants.values() for r in q]
        for q in self.tenants.values():
            q.clear()
        rejected, self._rejected = self._rejected, []
        requests = queued + rejected
        if not requests:
            return ZooReport(self.policy.name, (), (), 0.0, 0.0, 0.0, (),
                             placement=self.placement.name,
                             n_replicas=self.n_replicas)
        decisions, attempts, health, states, mesh_plans = [], [], {}, {}, []
        events = [FaultEvent(r.arrival_s, -1, r.model, "shed",
                             "stale deadline at submit", uids=(r.uid,))
                  for r in rejected]    # admission-time typed rejections
        if queued:
            with telemetry.span("zoo.schedule", call):
                decisions, attempts, sched_events, health, states, \
                    mesh_plans = self._schedule(queued)
            events.extend(sched_events)
        if execute:
            with telemetry.span("zoo.execute", call):
                self._execute(attempts, events, call)
        with telemetry.span("zoo.account", call):
            return self._account(requests, decisions, events, health,
                                 states, mesh_plans)

    def _account(self, requests: list[ZooRequest],
                 decisions: list[WaveDecision], events: list[FaultEvent],
                 health: dict[str, ModelHealth],
                 states: dict[str, _ReplicaState],
                 mesh_plans: list) -> ZooReport:
        # the zero-unaccounted guarantee, enforced defensively: anything
        # the scheduler somehow left non-terminal becomes a typed error
        # result rather than a silent drop
        for r in requests:
            if r.status not in ("served", "shed", "quarantined"):
                r.status, r.error = "quarantined", ServeError(
                    "internal: request left non-terminal by the "
                    "scheduler", uid=r.uid, model=r.model)
                events.append(FaultEvent(-1.0, -1, r.model, "quarantine",
                                         "internal: non-terminal request",
                                         uids=(r.uid,)))
        served = [r for r in requests if r.status == "served"]
        makespan = (max(r.finish_s for r in served)
                    - min(r.arrival_s for r in requests)) if served else 0.0
        by_tenant: dict[str, list[ZooRequest]] = {}
        for r in requests:
            by_tenant.setdefault(r.tenant, []).append(r)
        by_replica: dict[str, int] = {}
        for r in served:
            by_replica[r.replica] = by_replica.get(r.replica, 0) + 1
        return ZooReport(
            policy=self.policy.name,
            requests=tuple(sorted(requests, key=lambda r: r.uid)),
            decisions=tuple(decisions),
            makespan_s=makespan,
            conv_busy_s=sum(d.conv_s * max(1, len(d.shards))
                            for d in decisions),
            fc_busy_s=sum(d.fc_s * max(1, len(d.shards))
                          for d in decisions),
            per_tenant=tuple(_tenant_stats(t, rs) for t, rs in
                             sorted(by_tenant.items())),
            events=tuple(events),
            health=tuple((m, h.state) for m, h in sorted(health.items())),
            placement=self.placement.name,
            n_replicas=self.n_replicas,
            per_replica=tuple(
                ReplicaStats(replica=rid, waves=st.waves,
                             served=by_replica.get(rid, 0),
                             busy_s=st.busy_s, drained_away=st.drained_away,
                             state=st.state_name)
                for rid, st in sorted(states.items())),
            mesh_plans=tuple(mesh_plans))


def _tenant_stats(tenant: str, reqs: list[ZooRequest]) -> TenantStats:
    served = [r for r in reqs if r.status == "served"]
    lats = np.array([r.latency_s for r in served], dtype=np.float64)
    has = lats.size > 0
    return TenantStats(
        tenant=tenant, n=len(reqs),
        mean_latency_s=float(lats.mean()) if has else 0.0,
        p50_s=float(np.percentile(lats, 50)) if has else 0.0,
        p95_s=float(np.percentile(lats, 95)) if has else 0.0,
        p99_s=float(np.percentile(lats, 99)) if has else 0.0,
        deadlines=sum(r.deadline_s is not None for r in reqs),
        misses=sum(bool(r.missed_deadline) for r in reqs),
        served=len(served),
        shed=sum(r.status == "shed" for r in reqs),
        quarantined=sum(r.status == "quarantined" for r in reqs),
        retries=sum(r.retries for r in reqs),
        degraded=sum(r.degraded for r in served))


# the benchmark harness imports this name for its fleet cells
FleetServer = ModelZooServer
