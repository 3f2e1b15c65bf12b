"""Multi-tenant model-zoo serving: the tagged request, the wave
scheduling policies, the compiled model variants, and the records the
serving plane fills.

The paper's core claim is that *jointly* scheduling heterogeneous work
(CONV on SA-CONV, FC on SA-FC) beats optimizing either array in
isolation.  The zoo is the serving-side analogue: one engine holds
several **compiled model variants** at once (AlexNet fp32, VGG-16 fp32,
an int8 AlexNet, ...), admits a mixed stream of tagged requests into
per-tenant queues, and decides *which model's wave dispatches next* using
the planner's own data:

* each model's wave size is its planner-preferred micro-batch — the
  resident batch tile (:attr:`~repro.core.dataflow.FCPlan.bb`) one
  streamed FC weight pass amortizes over
  (:attr:`~repro.serve.cnn_server.CNNServer.preferred_microbatch`);
* each candidate wave is priced by the modeled dual-array stage costs
  (:func:`~repro.core.perf_model.zoo_wave_cost`), so the scheduler
  *knows* a VGG-16 wave occupies SA-CONV ~40x longer than an AlexNet
  wave and that the int8 variant's FC stream is 4x cheaper;
* a pluggable :class:`SchedulingPolicy` picks the next wave while the
  other array drains the previous one: :class:`FIFOPolicy`,
  :class:`ShortestMakespanPolicy` and :class:`EDFPolicy`.

The plane that runs them — admission control, the modeled-time
scheduling loop, retry with backoff, per-model health and int8 degraded
fallback, per-replica liveness, the executor and its integrity guard,
and the accounting that fills :class:`ZooReport` — is
:class:`repro.serve.fleet.ModelZooServer`, importable from here too.  A
zoo on one chip is its one-replica case; ``n_replicas`` spreads the same
plane over a fleet of devices.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable, Mapping, Sequence

import numpy as np

from repro.configs.registry import ZooModelSpec, get_zoo_model
from repro.core.engine import Engine
from repro.core.perf_model import (ShardedWaveCost, WaveCost,
                                   sharded_wave_cost, zoo_wave_cost)
from repro.serve.cnn_server import CNNServer
from repro.serve.errors import ServeError


@dataclasses.dataclass
class ZooRequest:
    """One tagged request of the mixed stream: which model, which tenant,
    when it arrived (virtual seconds), and optionally by when it must
    finish (``deadline_s``, absolute virtual time — the SLO).

    Every admitted request ends in exactly one terminal ``status``:
    ``"served"`` (logits delivered), ``"shed"`` (admission control
    rejected it) or ``"quarantined"`` (execution failed past the retry
    budget); ``error`` carries the typed cause for the latter two.
    ``allow_degraded`` opts the request into int8 fallback service;
    ``served_by`` records which variant actually served it; ``replica``
    records which replica it was last placed on (``"r0"`` in a
    single-replica zoo)."""
    uid: int
    model: str
    image: np.ndarray                     # (H, W, C) of the model's server
    tenant: str = "default"
    arrival_s: float = 0.0
    deadline_s: float | None = None
    allow_degraded: bool = True
    # -- filled by the scheduler/executor ----------------------------------
    dispatch_s: float | None = None    # SA-CONV start of its final wave
    finish_s: float | None = None      # SA-FC completion of its final wave
    logits: np.ndarray | None = None
    done: bool = False
    status: str = "pending"            # -> served | shed | quarantined
    error: ServeError | None = None
    retries: int = 0
    served_by: str | None = None       # variant that served it (may degrade)
    replica: str | None = None         # replica it was last placed on

    @property
    def latency_s(self) -> float | None:
        return None if self.finish_s is None \
            else self.finish_s - self.arrival_s

    @property
    def degraded(self) -> bool:
        """Served by a fallback variant instead of the requested one."""
        return self.served_by is not None and self.served_by != self.model

    @property
    def missed_deadline(self) -> bool | None:
        """None = no SLO attached; else whether the modeled completion
        blew the absolute deadline."""
        if self.deadline_s is None:
            return None
        return None if self.finish_s is None \
            else self.finish_s > self.deadline_s


@dataclasses.dataclass(frozen=True)
class WaveDecision:
    """One scheduler decision: at modeled time ``t_s`` replica
    ``replica`` dispatched ``model``'s wave of ``batch`` requests, priced
    at the modeled stage costs below.  The ordered decision list is the
    deterministic policy log the regression gate pins.  ``fault``
    annotates what the chaos layer did to the attempt (``"none"`` on the
    healthy path) and ``conv_s``/``fc_s`` are the *actual* modeled
    occupancies (stretched for a stall, zero for a failed dispatch).
    ``shards`` is empty for a one-replica wave; a cooperative sharded
    wave lists every participant (``replica`` is the root whose queue it
    was cut from) and carries the sharded stage terms."""
    index: int
    t_s: float
    model: str
    uids: tuple[int, ...]
    batch: int
    conv_s: float
    fc_s: float
    queue_depths: tuple[tuple[str, int], ...] = ()  # pending per model
    fault: str = "none"   # none|stall|timeout|corrupt|dispatch|replica_dead
    stall_factor: float = 1.0
    replica: str = "r0"
    shards: tuple[str, ...] = ()

    @property
    def total_s(self) -> float:
        return self.conv_s + self.fc_s

    @property
    def sharded(self) -> bool:
        return bool(self.shards)


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """One robustness-plane event in modeled time: a fault firing, or the
    server's response to one.  The ordered event list is deterministic
    and gated alongside the decision log.  ``kind`` is a wave fault
    (``stall``, ``timeout``, ``corrupt``, ``dispatch``), a per-request
    outcome (``retry``, ``quarantine``, ``shed``, ``degrade``), a model
    ``health`` transition, a replica transition (``kill``,
    ``replica_dead``, ``drain``, ``suspect``, ``rejoin``, ``replan``,
    ``replan_failed``) or a step of a cooperative wave (``shard_abort``,
    ``reshard``, ``shard_fallback``).  ``replica`` is ``"-"`` where no
    replica is concerned; ``model`` is empty for replica transitions."""
    t_s: float
    attempt: int                  # wave attempt index; -1 for admission
    model: str
    kind: str
    detail: str
    uids: tuple[int, ...] = ()
    replica: str = "-"


@dataclasses.dataclass(frozen=True)
class AdmissionConfig:
    """Admission-control policy.  ``max_queue`` bounds each tenant's
    pending (not-yet-dispatched) requests — overflow is shed with a typed
    :class:`~repro.serve.errors.RequestShedError`.  ``predictive_shedding``
    rejects a deadline request whose *best-case* completion (immediate
    dispatch, solo wave, the scheduler's own cost model) already misses —
    unless a degraded fallback variant would make it."""
    max_queue: int | None = None
    predictive_shedding: bool = False


@dataclasses.dataclass(frozen=True)
class RecoveryConfig:
    """Retry, straggler and health policy for the serving plane.

    A failed wave attempt re-queues its requests after
    ``min(backoff_cap_s, backoff_s * backoff_mult**(retries-1))``;
    a request failing more than ``max_retries`` attempts is quarantined.
    A stalled wave whose stretch factor reaches ``wave_timeout_factor``
    is aborted at the timeout (occupying both arrays that long) and
    counts as a failure; milder stalls complete late and feed the
    per-model :class:`~repro.distributed.fault_tolerance.StepMonitor`
    (``straggler_factor`` x running median over normalized wave times,
    after ``straggler_warmup`` observations).  ``fail_after`` consecutive
    failures — or ``heartbeat_timeout_s`` of modeled time without a
    completed wave while work is pending — mark a model ``failed``;
    ``recover_after`` clean waves walk it back to ``healthy``.
    ``allow_degraded`` enables rerouting a failed/infeasible fp32
    variant's eligible requests to the int8 variant of the same net."""
    max_retries: int = 2
    backoff_s: float = 2e-4
    backoff_mult: float = 2.0
    backoff_cap_s: float = 2e-3
    wave_timeout_factor: float = 8.0
    straggler_factor: float = 3.0
    straggler_warmup: int = 3
    straggler_window: int = 50
    fail_after: int = 2
    recover_after: int = 2
    heartbeat_timeout_s: float = 1.0
    allow_degraded: bool = True


@dataclasses.dataclass
class ModelHealth:
    """Per-model health state machine: ``healthy -> degraded -> failed``
    and back.  A straggler verdict degrades; ``fail_after`` consecutive
    wave failures (or a heartbeat timeout) fail; clean waves walk the
    state back up one level at a time (``failed -> degraded`` on the
    first clean wave, ``degraded -> healthy`` after ``recover_after``
    clean waves)."""
    model: str
    state: str = "healthy"
    consecutive_failures: int = 0
    clean_streak: int = 0
    straggler_waves: int = 0
    failed_waves: int = 0

    def on_clean(self, cfg: RecoveryConfig) -> str | None:
        old = self.state
        self.consecutive_failures = 0
        self.clean_streak += 1
        if self.state == "failed":
            self.state, self.clean_streak = "degraded", 0
        elif self.state == "degraded" \
                and self.clean_streak >= cfg.recover_after:
            self.state = "healthy"
        return self.state if self.state != old else None

    def on_straggler(self, cfg: RecoveryConfig) -> str | None:
        old = self.state
        self.straggler_waves += 1
        self.clean_streak = 0
        if self.state == "healthy":
            self.state = "degraded"
        return self.state if self.state != old else None

    def on_failure(self, cfg: RecoveryConfig) -> str | None:
        old = self.state
        self.failed_waves += 1
        self.consecutive_failures += 1
        self.clean_streak = 0
        if self.consecutive_failures >= cfg.fail_after:
            self.state = "failed"
        elif self.state == "healthy":
            self.state = "degraded"
        return self.state if self.state != old else None

    def force_failed(self) -> str | None:
        old = self.state
        self.state = "failed"
        self.consecutive_failures = 0
        self.clean_streak = 0
        return self.state if self.state != old else None


@dataclasses.dataclass
class WaveAttempt:
    """One scheduled wave attempt, as handed to the executor: the model,
    the replica lane that runs it (and every participant of a
    cooperative wave), the boarding requests (wave order = row order),
    the fault verdict (``None`` on the healthy path), and which uids this
    attempt actually serves (``deliver`` excludes corrupt rows; empty for
    failed attempts).  ``execute=False`` marks attempts that never ran to
    completion (dispatch failures, timeout aborts, a replica lost
    mid-wave) — the executor skips their kernels."""
    index: int
    model: str
    requests: list[ZooRequest]
    faults: object | None
    deliver: tuple[int, ...]
    execute: bool = True
    replica: str = "r0"
    shards: tuple[str, ...] = ()


class SchedulingPolicy:
    """Picks which model's wave dispatches next.  ``pick`` sees the
    non-empty pending queues (each in arrival order), the modeled clock,
    and a pricing callback ``cost(model, batch) -> WaveCost``; it returns
    a model name.  ``wave_order`` orders one model's queue before the
    wave is cut from its head (FIFO by arrival unless overridden)."""

    name = "base"

    def pick(self, now: float, pending: Mapping[str, list[ZooRequest]],
             cost: Callable[[str, int], WaveCost]) -> str:
        raise NotImplementedError

    def wave_order(self, reqs: list[ZooRequest]) -> list[ZooRequest]:
        return reqs

    @staticmethod
    def _head_key(q: list[ZooRequest]) -> tuple[float, int]:
        return (q[0].arrival_s, q[0].uid)


class FIFOPolicy(SchedulingPolicy):
    """Oldest head-of-queue request first — the baseline every SLO/latency
    comparison in BENCH_zoo.json is against."""

    name = "fifo"

    def pick(self, now, pending, cost):
        return min(pending, key=lambda m: (*self._head_key(pending[m]), m))


class ShortestMakespanPolicy(SchedulingPolicy):
    """Cheapest predicted wave first: price the wave each candidate model
    would dispatch (its queue head cut at the model's micro-batch) with
    the modeled dual-array stage costs and run the smallest total.  The
    classic SJF mean-latency argument, with the planner's own cost model
    as the job-size oracle."""

    name = "smf"

    def pick(self, now, pending, cost):
        return min(pending,
                   key=lambda m: (cost(m, len(pending[m])).total_s,
                                  *self._head_key(pending[m]), m))


class EDFPolicy(SchedulingPolicy):
    """Earliest deadline first: the model owning the most urgent pending
    request dispatches next, and inside that model's queue the
    tightest-deadline requests board the wave first.  Requests without a
    deadline sort last (best effort)."""

    name = "edf"

    @staticmethod
    def _urgency(r: ZooRequest) -> tuple[float, float, int]:
        d = r.deadline_s if r.deadline_s is not None else float("inf")
        return (d, r.arrival_s, r.uid)

    def pick(self, now, pending, cost):
        return min(pending,
                   key=lambda m: (min(self._urgency(r) for r in pending[m]),
                                  m))

    def wave_order(self, reqs):
        return sorted(reqs, key=self._urgency)


POLICIES: dict[str, Callable[[], SchedulingPolicy]] = {
    "fifo": FIFOPolicy, "smf": ShortestMakespanPolicy, "edf": EDFPolicy,
}


class ZooModel:
    """One compiled model variant held by the zoo: the registry spec, its
    (possibly width-scaled) parameters, the per-model
    :class:`~repro.serve.cnn_server.CNNServer` wave executor, and the
    modeled wave-cost pricing the scheduler consults.  The cost model
    always prices the *full-geometry* variant (``spec.weight_bytes``
    narrows the int8 FC stream) — the scheduler reasons about the model,
    not about the shrunken test instantiation executing it."""

    def __init__(self, spec: ZooModelSpec, params: list, *,
                 in_res: int | None = None, width_mult: float = 1.0,
                 max_batch: int = 8,
                 engine: Engine | None = None) -> None:
        self.spec = spec
        self.name = spec.name
        self.params = params
        self.server = CNNServer(spec.net, params, in_res=in_res,
                                width_mult=width_mult, max_batch=max_batch,
                                engine=engine)

    @property
    def microbatch(self) -> int:
        """The wave size the scheduler cuts for this model — its server's
        planner-preferred micro-batch (public, satellite of PR 4's bb)."""
        return self.server.microbatch

    def sharded_microbatch(self, data: int) -> int:
        """The wave size a *cooperative* sharded wave may grow to when
        ``data`` replicas execute it together: each replica still holds
        its planner-preferred resident tile (``bb`` rows), so the fleet
        wave is ``data x microbatch`` — the only place a zoo wave is
        allowed to exceed :attr:`microbatch`."""
        if data < 1:
            raise ValueError(f"data must be >= 1, got {data}")
        return self.microbatch * data

    def wave_cost(self, batch: int) -> WaveCost:
        """Modeled dual-array stage cost of one ``batch``-sample wave of
        this variant (memoized in perf_model)."""
        return zoo_wave_cost(self.spec.net, batch,
                             bytes_w=self.spec.weight_bytes)

    def sharded_wave_cost(self, batch: int, data: int) -> ShardedWaveCost:
        """Modeled cost of one cooperative ``data``-way sharded wave of
        this variant vs. independent per-replica waves (see
        :func:`~repro.core.perf_model.sharded_wave_cost`)."""
        return sharded_wave_cost(self.spec.net, batch, data,
                                 microbatch=self.microbatch,
                                 bytes_w=self.spec.weight_bytes)


def build_zoo(names: Sequence[str], *, seed: int = 0,
              in_res: Mapping[str, int] | None = None,
              width_mult: float = 1.0, max_batch: int = 8,
              engine: Engine | None = None) -> list[ZooModel]:
    """Instantiate zoo models from the registry by name (seeded params;
    int8 variants quantized per-channel via
    :func:`~repro.core.quant.quantize_cnn_params`).  ``in_res`` maps net
    name -> serving resolution (default: the spec's native resolution);
    ``width_mult`` scales every model identically so tests/benches can
    shrink execution without touching the cost model."""
    import jax

    from repro.core.quant import quantize_cnn_params
    from repro.models import cnn

    out = []
    for i, name in enumerate(names):
        spec = get_zoo_model(name)
        res = (in_res or {}).get(spec.net, spec.in_res)
        params = cnn.init_cnn(spec.net, jax.random.PRNGKey(seed + i),
                              in_res=res, width_mult=width_mult)
        if spec.weight_dtype == "int8":
            params = quantize_cnn_params(params)
        out.append(ZooModel(spec, params, in_res=res,
                            width_mult=width_mult, max_batch=max_batch,
                            engine=engine))
    return out


@dataclasses.dataclass(frozen=True)
class TenantStats:
    tenant: str
    n: int
    mean_latency_s: float
    p50_s: float
    p95_s: float
    p99_s: float
    deadlines: int
    misses: int
    served: int = 0
    shed: int = 0
    quarantined: int = 0
    retries: int = 0
    degraded: int = 0

    @property
    def miss_rate(self) -> float:
        return self.misses / self.deadlines if self.deadlines else 0.0

    @property
    def shed_rate(self) -> float:
        return self.shed / self.n if self.n else 0.0


@dataclasses.dataclass(frozen=True)
class ReplicaStats:
    """Per-replica accounting for one drain: waves dispatched, requests
    served, modeled busy seconds, requests drained *away* from it, and
    its final state (``alive`` | ``suspect`` | ``dead``)."""
    replica: str
    waves: int
    served: int
    busy_s: float
    drained_away: int
    state: str


@dataclasses.dataclass(frozen=True)
class ZooReport:
    """Everything one ``serve()`` drain produced: the admitted requests
    (each in exactly one terminal status), the ordered decision log, the
    robustness event log, and the modeled accounting — per-tenant latency
    percentiles, deadline misses, shed / quarantine / degradation counts,
    per-array utilization, final per-model health, per-replica stats and
    the elastic mesh-plan history ``(t_s, data_degree, wasted_chips,
    why)``."""
    policy: str
    requests: tuple[ZooRequest, ...]
    decisions: tuple[WaveDecision, ...]
    makespan_s: float
    conv_busy_s: float
    fc_busy_s: float
    per_tenant: tuple[TenantStats, ...]
    events: tuple[FaultEvent, ...] = ()
    health: tuple[tuple[str, str], ...] = ()   # final per-model state
    placement: str = "least-loaded"
    n_replicas: int = 1
    per_replica: tuple[ReplicaStats, ...] = ()
    mesh_plans: tuple[tuple[float, int, int, str], ...] = ()

    @property
    def served(self) -> tuple[ZooRequest, ...]:
        return tuple(r for r in self.requests if r.status == "served")

    @property
    def shed(self) -> tuple[ZooRequest, ...]:
        return tuple(r for r in self.requests if r.status == "shed")

    @property
    def quarantined(self) -> tuple[ZooRequest, ...]:
        return tuple(r for r in self.requests if r.status == "quarantined")

    @property
    def unaccounted(self) -> tuple[ZooRequest, ...]:
        """Admitted requests in no terminal state — ALWAYS empty (the
        zero-unaccounted guarantee); exposed so benches can gate it."""
        terminal = ("served", "shed", "quarantined")
        return tuple(r for r in self.requests if r.status not in terminal)

    @property
    def shed_rate(self) -> float:
        return len(self.shed) / len(self.requests) if self.requests else 0.0

    @property
    def retry_count(self) -> int:
        return sum(r.retries for r in self.requests)

    @property
    def degraded_served(self) -> int:
        return sum(r.degraded for r in self.served)

    @property
    def degraded_waves(self) -> int:
        """Scheduler decisions whose attempt was faulted (annotated by
        the chaos layer) — the wave-level degradation count."""
        return sum(d.fault != "none" for d in self.decisions)

    @property
    def drained_uids(self) -> tuple[int, ...]:
        """Requests moved off a dying or suspect replica (queued drains
        plus in-flight ``replica_dead`` losses), in event order."""
        out: list[int] = []
        for e in self.events:
            if e.kind in ("drain", "replica_dead"):
                out.extend(u for u in e.uids if u not in out)
        return tuple(out)

    @property
    def throughput_rps(self) -> float:
        return len(self.served) / self.makespan_s if self.makespan_s \
            else 0.0

    @property
    def mean_latency_s(self) -> float:
        lats = [r.latency_s for r in self.served]
        return float(np.mean(lats)) if lats else 0.0

    @property
    def deadline_misses(self) -> int:
        return sum(bool(r.missed_deadline) for r in self.requests)

    @property
    def deadline_count(self) -> int:
        return sum(r.deadline_s is not None for r in self.requests)

    @property
    def miss_rate(self) -> float:
        n = self.deadline_count
        return self.deadline_misses / n if n else 0.0

    @property
    def conv_utilization(self) -> float:
        """Busy share of the replicas' SA-CONV arrays over the makespan."""
        span = self.makespan_s * self.n_replicas
        return self.conv_busy_s / span if span else 0.0

    @property
    def fc_utilization(self) -> float:
        span = self.makespan_s * self.n_replicas
        return self.fc_busy_s / span if span else 0.0

    def summary(self) -> str:
        lines = [f"[zoo:{self.placement}/{self.policy}] {self.n_replicas} "
                 f"replica(s), {len(self.requests)} requests in "
                 f"{len(self.decisions)} waves, makespan "
                 f"{self.makespan_s * 1e3:.3f} ms, mean latency "
                 f"{self.mean_latency_s * 1e3:.3f} ms, misses "
                 f"{self.deadline_misses}/{self.deadline_count}, "
                 f"util conv {self.conv_utilization:.2f} / "
                 f"fc {self.fc_utilization:.2f}"]
        if self.shed or self.quarantined or self.events:
            lines.append(f"  robustness: served {len(self.served)} shed "
                         f"{len(self.shed)} quarantined "
                         f"{len(self.quarantined)}, retries "
                         f"{self.retry_count}, degraded-served "
                         f"{self.degraded_served}, faulted waves "
                         f"{self.degraded_waves}, drained "
                         f"{len(self.drained_uids)}")
        for t in self.per_tenant:
            lines.append(f"  tenant {t.tenant}: n={t.n} p50 "
                         f"{t.p50_s * 1e3:.3f} ms p95 {t.p95_s * 1e3:.3f} "
                         f"ms p99 {t.p99_s * 1e3:.3f} ms "
                         f"misses {t.misses}/{t.deadlines}")
        for s in self.per_replica:
            lines.append(f"  {s.replica}[{s.state}]: waves={s.waves} "
                         f"served={s.served} busy "
                         f"{s.busy_s * 1e3:.3f} ms "
                         f"drained-away={s.drained_away}")
        for t_s, data, wasted, why in self.mesh_plans[1:]:
            lines.append(f"  mesh@{t_s * 1e3:.3f}ms: data={data} "
                         f"wasted={wasted} ({why})")
        return "\n".join(lines)


def __getattr__(name: str):
    # the plane lives in repro.serve.fleet, which imports this module
    if name == "ModelZooServer":
        from repro.serve.fleet import ModelZooServer
        return ModelZooServer
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
