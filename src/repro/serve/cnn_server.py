"""Micro-batch coalescing CNN server — batched image serving on the
batch-amortized SA-FC dataflow, pipelined across the two arrays.

The paper's SA-FC array only wins when each streamed weight byte is
amortized across a batch of samples: per-sample FC weight reuse is 1
(Sec. V-A), and AlexNet's classifier head holds ~58.6M of its ~62M
weights, so single-image serving is bound by re-streaming the FC matrices
per request.  This server is the CNN analogue of
:class:`repro.serve.engine.ServeEngine`:

* single-image requests queue up and are coalesced into the **planner's
  preferred micro-batch** — the resident batch tile
  (:attr:`~repro.core.dataflow.FCPlan.bb`) the policy's VMEM budget
  affords the dominant FC layer, i.e. exactly the number of samples one
  weight pass can serve;
* each admission wave runs as TWO pipeline stages under memoized
  stage-split :meth:`~repro.core.schedule.LayerSchedule.compile_cnn`
  schedules: the SA-CONV stage (conv+fused-pool stack -> flattened
  features, the stage hand-off buffer) and the SA-FC stage (classifier
  head on the buffered features), each one compiled program per wave
  size (:func:`~repro.models.cnn.cnn_conv_stage`,
  :func:`~repro.models.cnn.cnn_fc_stage`): a wave makes one call per
  stage, not one dispatch per layer;
* **dual-array pipelining** (the paper's joint execution: both arrays
  busy at once): wave *i*'s FC head is dispatched and completed while
  wave *i+1*'s conv stack is already in flight — the conv stage of the
  next wave is enqueued (JAX async dispatch) *before* the previous
  wave's FC stage is drained, so on an asynchronous backend the SA-CONV
  and SA-FC work overlap.  ``pipeline=False`` (or ``run(pipelined=
  False)``) keeps the strictly sequential order for A/B;
* per-request outputs are **bitwise equal** on both paths and to the
  unbatched forward: the stages run the same kernels under the same
  plans in the same per-wave order — pipelining changes *when* a stage
  is waited on, never what it computes.

Every wave's :class:`~repro.core.engine.DispatchTrace` is kept on the
:class:`WaveReport`, with each record tagged by the pipeline stage and
wave that dispatched it (``stage='conv'|'fc'``, ``wave=i``) — the
serving-side twin of the stage-split schedule tables.  The server keeps
the reports of its most recent :data:`RECENT_WAVES` waves.

Each wave's host work is marked with :mod:`repro.serve.telemetry` spans
(ident = the wave index): ``cnn.wave`` (one :meth:`CNNServer.step_wave`,
counting its rows as ``cnn.rows``), ``cnn.upload`` (the image stack),
``cnn.conv_dispatch`` / ``cnn.fc_dispatch`` (the host calling each
stage's program, counted as ``cnn.stage_calls``, and tracing it where
that wave size is new, counted as ``cnn.stage_builds``; the conv
dispatch counts each conv's row bands, input slab bytes and halo bytes
as ``sa_conv.bands``, ``sa_conv.slab_bytes`` and ``sa_conv.halo_bytes``)
and ``cnn.logits_wait`` (the wave's one barrier).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.dataflow import band_input_bytes
from repro.core.engine import DispatchTrace, Engine
from repro.core.schedule import LayerSchedule
from repro.serve import telemetry

#: the wave reports a server keeps (``CNNServer.waves``): the most recent
RECENT_WAVES = 256


@dataclasses.dataclass
class CNNRequest:
    """One single-image classification request."""
    uid: int
    image: np.ndarray                     # (H, W, C)
    done: bool = False
    logits: np.ndarray | None = None


@dataclasses.dataclass(frozen=True)
class WaveReport:
    """What one coalesced dispatch did: who rode it, how it resolved.

    ``conv_trace``/``fc_trace`` are the per-stage views the pipeline
    hands between arrays; ``trace`` joins them into the wave's full
    dispatch picture (conv stage then FC stage, every record
    stage/wave-tagged) when it is read."""
    uids: tuple[int, ...]
    batch: int
    schedule_hits: int
    conv_trace: DispatchTrace
    fc_trace: DispatchTrace
    wave: int = 0

    @property
    def trace(self) -> DispatchTrace:
        joined = DispatchTrace()
        joined.records = [*self.conv_trace, *self.fc_trace]
        return joined

    @property
    def fc_records(self):
        """The FC dispatches of this wave (each carries its FCPlan)."""
        return [r for r in self.trace if r.fc_plan is not None]


@dataclasses.dataclass
class _StageBuffer:
    """The explicit hand-off buffer between the two pipeline stages: one
    wave's requests plus its in-flight conv-stage output (flattened
    features, NOT blocked on) and the conv-stage trace."""
    wave: int
    requests: list[CNNRequest]
    feats: object                         # jax.Array, possibly in flight
    conv_trace: DispatchTrace


def _count_bands(trace: DispatchTrace) -> None:
    """Count, per dispatched conv, the row bands its plan ran in
    (``sa_conv.bands``), its compulsory input slab bytes
    (``sa_conv.slab_bytes``) and the input bytes fetched a second time as
    band halos (``sa_conv.halo_bytes``)."""
    for r in trace:
        if r.conv_plan is None:
            continue
        batch, h, w, ci, p, q, _, stride = r.conv_shape
        slab, halo = band_input_bytes(
            batch, h, w, ci, p, q, stride=stride, band=r.conv_plan.band,
            bytes_in=np.dtype(r.dtype).itemsize)
        telemetry.count("sa_conv.bands", r.conv_plan.bands)
        telemetry.count("sa_conv.slab_bytes", slab)
        telemetry.count("sa_conv.halo_bytes", halo)


class CNNServer:
    """Admit single images, dispatch planner-sized micro-batches through
    the dual-array two-stage pipeline.

    ``max_batch`` caps admission; the actual micro-batch is the planner's
    resident batch tile for the network's dominant FC layer under the
    engine's policy (a tight ``vmem_budget`` shrinks it — the server
    admits exactly what one weight pass can amortize over).

    ``pipeline`` selects the default :meth:`run` mode: ``True`` overlaps
    wave *i*'s SA-FC stage with wave *i+1*'s SA-CONV stage (the paper's
    joint dual-array execution), ``False`` drains each wave's two stages
    back-to-back.  Logits are bitwise identical either way."""

    def __init__(self, net: str, params: list, *,
                 in_res: int | None = None, in_ch: int = 3,
                 width_mult: float = 1.0, max_batch: int = 64,
                 dtype=jnp.float32,
                 pipeline: bool = True,
                 engine: Engine | None = None) -> None:
        from repro.models import cnn
        spec, res0 = cnn.NETWORKS[net]
        self.net = net
        self.params = params
        self.in_res = in_res if in_res is not None else res0
        self.in_ch = in_ch
        self.width_mult = width_mult
        self.max_batch = max_batch
        self.dtype = jnp.dtype(dtype)
        self.pipeline = pipeline
        self.engine = engine if engine is not None \
            else Engine(backend="pallas")
        self._planner_microbatch = self._preferred_microbatch()
        self.microbatch = self._planner_microbatch
        # Plan the conv stack now: a layer not even a band of rows fits on
        # the chip raises PlanError naming it here, not inside a served
        # wave.  Conv VMEM does not depend on the batch, so batch 1 suffices.
        LayerSchedule.compile_cnn(
            net, stage="conv", batch=1, in_res=self.in_res, in_ch=in_ch,
            width_mult=width_mult, dtype=self.dtype,
            policy=self.engine.policy, params=params)
        self._schedules: dict[int, tuple[LayerSchedule, LayerSchedule]] = {}
        self.queue: list[CNNRequest] = []
        self.waves: list[WaveReport] = []        # the RECENT_WAVES latest
        self._wave_counter = 0
        self._uids: set = set()
        self._inflight: _StageBuffer | None = None

    @property
    def preferred_microbatch(self) -> int:
        """The planner's resident batch tile for this model's dominant FC
        layer under the engine's policy — the wave size one streamed
        weight pass amortizes over.  Public so a multi-model scheduler
        (:mod:`repro.serve.zoo`) can size waves without reaching into the
        planner; ``self.microbatch`` (initialized to this) is the mutable
        admission cap actually used."""
        return self._planner_microbatch

    # -- planning -----------------------------------------------------------
    def _fc_shapes(self) -> list[tuple[int, int, int]]:
        """(k, n, weight_bytes) of every FC layer, read off the actual
        parameters (the width-scaled geometry, not the paper table).
        int8 :class:`~repro.core.quant.QTensor` weights report their real
        1-byte stream cost — the planner sizes the micro-batch for the
        bytes that actually cross HBM."""
        from repro.core.quant import QTensor
        from repro.models import cnn
        spec, _ = cnn.NETWORKS[self.net]
        out = []
        for s, p in zip(spec, self.params):
            if s.kind != "fc":
                continue
            w = p["w"]
            if isinstance(w, QTensor):
                out.append((*w.q.shape, 1))
            else:
                out.append((*w.shape, jnp.dtype(w.dtype).itemsize))
        return out

    def _preferred_microbatch(self) -> int:
        """Plan the dominant (largest ``k*n``) FC layer at the admission
        cap and admit the batch tile the plan keeps resident per weight
        pass — the samples one streamed weight byte serves."""
        k, n, wb = max(self._fc_shapes(), key=lambda s: s[0] * s[1])
        ab = self.dtype.itemsize
        plan = self.engine.policy.plan_fc(self.max_batch, n, k,
                                          act_bytes=ab, weight_bytes=wb,
                                          regime="sa_fc")
        return max(1, min(self.max_batch, plan.bb))

    def _stage_schedules(self, batch: int
                         ) -> tuple[LayerSchedule, LayerSchedule]:
        """The stage schedules of a ``batch``-row wave, looked up once per
        row count: the memo's key fingerprints every parameter."""
        pair = self._schedules.get(batch)
        if pair is None:
            pair = self._schedules[batch] = LayerSchedule.compile_cnn_stages(
                self.net, batch=batch, in_res=self.in_res, in_ch=self.in_ch,
                width_mult=self.width_mult, dtype=self.dtype,
                policy=self.engine.policy, params=self.params)
        return pair

    # -- serving ------------------------------------------------------------
    def submit(self, req: CNNRequest) -> None:
        """Admit one request.  Duplicate uids are REJECTED (``ValueError``):
        a uid names one request for the lifetime of the server — waves,
        traces and zoo accounting all key on it, so re-submitting a uid
        would silently alias two requests in every report."""
        shape = (self.in_res, self.in_res, self.in_ch)
        if tuple(req.image.shape) != shape:
            raise ValueError(f"request {req.uid}: image shape "
                             f"{tuple(req.image.shape)} != server {shape}")
        if req.uid in self._uids:
            raise ValueError(f"duplicate request uid {req.uid}: uids are "
                             "unique per server lifetime")
        self._uids.add(req.uid)
        self.queue.append(req)

    def _conv_stage_dispatch(self, wave_idx: int,
                             wave: list[CNNRequest]) -> _StageBuffer:
        """Stage 1 (SA-CONV array): dispatch the conv+fused-pool stack of
        one wave and hand the (possibly still in-flight) flattened
        features to the stage buffer — no blocking here, so the next
        stage can be issued while this one runs."""
        from repro.models import cnn
        with telemetry.span("cnn.upload", wave_idx):
            x = jnp.stack([jnp.asarray(r.image, self.dtype) for r in wave])
        conv_sched, _ = self._stage_schedules(len(wave))
        eng = self.engine.with_schedule(conv_sched)
        with eng.tracing() as tr, eng.tagging(stage="conv", wave=wave_idx), \
                telemetry.span("cnn.conv_dispatch", wave_idx):
            feats = cnn.cnn_conv_stage(self.net, self.params, x, eng=eng)
            _count_bands(tr)
        return _StageBuffer(wave_idx, list(wave), feats, tr)

    def _fc_stage_complete(self, buf: _StageBuffer) -> list[CNNRequest]:
        """Stage 2 (SA-FC array): run the classifier head on the buffered
        features, block, deliver logits, and file the WaveReport."""
        from repro.models import cnn
        _, fc_sched = self._stage_schedules(len(buf.requests))
        eng = self.engine.with_schedule(fc_sched)
        with eng.tracing() as tr, eng.tagging(stage="fc", wave=buf.wave), \
                telemetry.span("cnn.fc_dispatch", buf.wave):
            logits = cnn.cnn_fc_stage(self.net, self.params, buf.feats,
                                      eng=eng)
        with telemetry.span("cnn.logits_wait", buf.wave):
            logits = np.asarray(logits)               # the pipeline barrier
        for i, r in enumerate(buf.requests):
            r.logits = logits[i]
            r.done = True
        if len(self.waves) >= RECENT_WAVES:
            del self.waves[0]
        self.waves.append(WaveReport(
            uids=tuple(r.uid for r in buf.requests),
            batch=len(buf.requests),
            schedule_hits=sum(r.schedule == "hit"
                              for t in (buf.conv_trace, tr) for r in t),
            conv_trace=buf.conv_trace, fc_trace=tr, wave=buf.wave))
        return buf.requests

    def step_wave(self) -> list[CNNRequest]:
        """Dispatch and complete ONE wave (up to ``microbatch`` requests,
        both stages, blocking); returns its completed requests, ``[]`` on
        an empty queue.  Any in-flight pipelined wave is completed first
        so wave order is preserved.  This is the wave-executor entry the
        multi-tenant zoo scheduler drives: the *zoo* decides which
        model's wave dispatches next, the model's server executes it.

        A stage that raises never loses requests: the wave's undelivered
        requests are pushed back to the head of the queue before the
        exception propagates, so the caller can retry, cancel, or
        quarantine them — the queue never silently wedges."""
        if self._inflight is None and not self.queue:
            return []
        with telemetry.span("cnn.wave", self._wave_counter):
            return self._step_wave()

    def _step_wave(self) -> list[CNNRequest]:
        finished: list[CNNRequest] = []
        if self._inflight is not None:
            buf, self._inflight = self._inflight, None
            try:
                finished.extend(self._fc_stage_complete(buf))
            except Exception:
                self.queue[:0] = [r for r in buf.requests if not r.done]
                raise
        if not self.queue:
            return finished
        wave = self.queue[:self.microbatch]
        self.queue = self.queue[len(wave):]
        telemetry.count("cnn.rows", len(wave))
        try:
            buf = self._conv_stage_dispatch(self._wave_counter, wave)
            self._wave_counter += 1
            finished.extend(self._fc_stage_complete(buf))
        except Exception:
            self.queue[:0] = [r for r in wave if not r.done]
            raise
        return finished

    def cancel(self, uids) -> list[CNNRequest]:
        """Remove still-queued requests by uid and return them (uids stay
        consumed — a cancelled uid names that request forever).  The zoo's
        recovery path uses this to pull a failed wave's requests out of
        the executor before quarantining them; unknown or already-served
        uids are ignored."""
        uids = set(uids)
        cancelled = [r for r in self.queue if r.uid in uids]
        self.queue = [r for r in self.queue if r.uid not in uids]
        return cancelled

    def drain(self) -> list[CNNRequest]:
        """Flush the server: complete the in-flight pipelined wave (if
        any), then serve everything still queued — including the final
        partial wave smaller than the planner's micro-batch.  Explicit
        and public so a zoo scheduler can flush a tenant's tail without
        poking at private stage buffers; ``run()`` ends with it."""
        finished: list[CNNRequest] = []
        if self._inflight is not None:
            finished.extend(self._fc_stage_complete(self._inflight))
            self._inflight = None
        while self.queue:
            finished.extend(self.step_wave())
        return finished

    def run(self, *, pipelined: bool | None = None) -> list[CNNRequest]:
        """Drain the queue in planner-preferred micro-batches; returns the
        completed requests (``[]`` for an empty queue).

        Pipelined (default, per ``self.pipeline``): wave *i+1*'s conv
        stage is dispatched BEFORE wave *i*'s FC stage is drained, so the
        SA-FC work of one wave overlaps the SA-CONV work of the next —
        one stage buffer deep, the paper's two-array occupancy.
        Sequential: each wave's two stages complete back-to-back.  The
        per-request logits are bitwise identical in both modes."""
        pipelined = self.pipeline if pipelined is None else pipelined
        finished: list[CNNRequest] = []
        while self.queue:
            wave = self.queue[:self.microbatch]
            self.queue = self.queue[len(wave):]
            buf = self._conv_stage_dispatch(self._wave_counter, wave)
            self._wave_counter += 1
            if self._inflight is not None:
                finished.extend(self._fc_stage_complete(self._inflight))
            self._inflight = buf
            if not pipelined:
                finished.extend(self._fc_stage_complete(self._inflight))
                self._inflight = None
        finished.extend(self.drain())
        return finished
