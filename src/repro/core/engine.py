"""Explicit heterogeneous execution engine — MPNA's array dispatch as an
object API.

The paper integrates two systolic arrays and assigns each layer to the one
whose dataflow matches the layer's reuse pattern (CONV -> SA-CONV,
FC -> SA-FC) in an *offline, per-layer schedule* (Sec. V).  This module is
the runtime half of that design:

* :class:`Engine` — owns the execution configuration (``chip``,
  ``backend``), a pluggable :class:`DispatchPolicy` (the
  SA-CONV/SA-FC classifier + Case-1..4 planner), an optional compiled
  :class:`repro.core.schedule.LayerSchedule`, and a structured
  :class:`DispatchTrace`.  ``engine.matmul`` / ``engine.attention`` are
  methods; every dense projection in every model runs through them.
* :class:`DispatchPolicy` — how an op is classified (compulsory arithmetic
  intensity vs. the chip ridge point) and planned.  Swap the chip model,
  the VMEM budget, or force a regime without touching call sites — the
  reconfigurability that CARLA (arXiv:2010.00627) and the Multi-Mode
  Inference Engine (arXiv:1712.03994) treat as first-class.
* :class:`DispatchTrace` / :class:`DispatchRecord` — "which array did this
  layer run on" as structured data, exactly like the paper's per-layer
  schedule table.  Records carry the weight dtype, the plan case, and
  whether the decision came from a compiled schedule (``hit``) or was
  re-planned on the fly (``miss``).

int8 weights (:class:`repro.core.quant.QTensor`) flow into the Pallas
kernels **un-dequantized**: the kernel streams the int8 bytes from HBM and
fuses the per-channel scale into its accumulator-flush epilogue, so the
weight stream is 1 byte/weight and the policy classifies the regime with
1 byte/weight.

Model code that cannot thread an ``Engine`` through its call graph uses
:func:`current` — an explicit, engine-object stack pushed/popped by
:meth:`Engine.activate`.  The legacy module-level ``matmul`` /
``attention`` functions and the ``execution()`` / ``dispatch_trace()``
context managers remain as thin deprecation shims over that stack so
existing call sites keep working during the migration.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from dataclasses import dataclass
from collections.abc import Iterator
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import dataflow
from repro.core.accelerator import TPU_V5E, TPUChip, current_chip
from repro.core.dataflow import ConvPlan, FCPlan, MatmulPlan, PoolSpec
from repro.kernels import ref
from repro.kernels.pool_act import maxpool_act
from repro.kernels.sa_conv import sa_conv_matmul
from repro.kernels.sa_conv_implicit import sa_conv_implicit
from repro.kernels.sa_fc import sa_fc_matmul


# ---------------------------------------------------------------------------
# structured dispatch trace
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class DispatchRecord:
    """One dispatch decision.  Supports ``rec["regime"]`` for
    backward-compatibility with the dict-based trace."""
    name: str
    regime: str                 # 'sa_conv' | 'sa_fc' | 'attention' | 'pool'
    m: int
    n: int
    k: int
    case: int
    backend: str
    dtype: str = ""             # activation dtype
    weight_dtype: str = ""      # 'int8' for QTensor weights
    schedule: str = ""          # 'hit' | 'miss' | '' (no schedule attached)
    plan: MatmulPlan | None = None
    # FC dispatches routed to the batch-amortized SA-FC dataflow carry the
    # batch-tiled plan (weight stream charged once per batch tile) instead
    # of a MatmulPlan
    fc_plan: FCPlan | None = None
    # CONV dispatches: the conv plan plus the layer geometry
    # (batch, h, w, ci, p, q, co, stride) — h/w are the padded input dims.
    conv_plan: ConvPlan | None = None
    conv_shape: tuple[int, ...] | None = None
    # the maxpool stage requested to ride this conv's flush epilogue; the
    # accepted/declined decision is conv_plan.fuse_pool
    pool: PoolSpec | None = None
    # dual-array pipeline tags (set via Engine.tagging): which pipeline
    # stage issued this dispatch ('conv' | 'fc' | '') and which serving
    # wave it belongs to (-1 = untagged)
    stage: str = ""
    wave: int = -1

    def __getitem__(self, key: str) -> Any:
        return getattr(self, key)

    def get(self, key: str, default: Any = None) -> Any:
        return getattr(self, key, default)


class DispatchTrace:
    """Ordered record of every dispatch decision made under an engine.

    Behaves like a list of :class:`DispatchRecord` (iteration, indexing,
    ``len``) so code written against the old list-of-dicts trace keeps
    working unchanged."""

    def __init__(self) -> None:
        self.records: list[DispatchRecord] = []

    def append(self, rec: DispatchRecord) -> None:
        self.records.append(rec)

    def __iter__(self) -> Iterator[DispatchRecord]:
        return iter(self.records)

    def __len__(self) -> int:
        return len(self.records)

    def __getitem__(self, i):
        return self.records[i]

    def by_regime(self, regime: str) -> list[DispatchRecord]:
        return [r for r in self.records if r.regime == regime]

    def by_stage(self, stage: str) -> list[DispatchRecord]:
        """Records a given pipeline stage dispatched ('conv' | 'fc')."""
        return [r for r in self.records if r.stage == stage]

    def by_wave(self, wave: int) -> list[DispatchRecord]:
        """Records a given serving wave dispatched."""
        return [r for r in self.records if r.wave == wave]

    def counts(self) -> dict:
        out: dict = {}
        for r in self.records:
            out[r.regime] = out.get(r.regime, 0) + 1
        return out

    def summary(self) -> str:
        lines = []
        for r in self.records:
            fused = ""
            if r.conv_plan is not None and r.conv_plan.fuse_pool:
                fused = (f" +pool{r.conv_plan.pool_window}"
                         f"s{r.conv_plan.pool_stride}")
            elif r.pool is not None and r.conv_plan is not None:
                fused = " pool-declined"
            elif r.fc_plan is not None:
                fused = (f" bb={r.fc_plan.bb}"
                         f" wx{r.fc_plan.weight_passes}")
            lines.append(f"{r.name:24s} {r.regime:9s} case={r.case} "
                         f"({r.m}x{r.k})@({r.k}x{r.n}) "
                         f"w={r.weight_dtype or '-'} "
                         f"{r.schedule or 'planned'}{fused}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# dispatch policy
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class DispatchPolicy:
    """Pluggable SA-CONV/SA-FC classification + Case-1..4 planning.

    ``chip`` supplies the ridge point and default VMEM budget;
    ``vmem_budget`` overrides the planner's on-chip allowance;
    ``force_regime`` pins every op to one array (ablations / tests);
    ``overrides`` pins ops by exact name, mirroring the per-layer
    exceptions a hand-tuned offline schedule would carry."""
    chip: TPUChip = TPU_V5E
    vmem_budget: int | None = None
    force_regime: str | None = None          # 'sa_conv' | 'sa_fc'
    overrides: tuple[tuple[str, str], ...] = ()  # (op name -> regime)

    def __post_init__(self) -> None:
        regimes = (None, "sa_conv", "sa_fc")
        if self.force_regime not in regimes:
            raise ValueError(f"force_regime must be one of {regimes[1:]}, "
                             f"got {self.force_regime!r}")
        for name, reg in self.overrides:
            if reg not in regimes[1:]:
                raise ValueError(f"override {name!r} names unknown regime "
                                 f"{reg!r}; must be one of {regimes[1:]}")

    def regime_for(self, name: str, m: int, n: int, k: int, *,
                   act_bytes: int, weight_bytes: int | None = None) -> str:
        for pat, reg in self.overrides:
            if name == pat:
                return reg
        if self.force_regime is not None:
            return self.force_regime
        return dataflow.classify_regime(m, n, k, act_bytes, self.chip,
                                        bytes_w=weight_bytes)

    def plan(self, m: int, n: int, k: int, *, act_bytes: int,
             weight_bytes: int | None = None,
             regime: str | None = None) -> MatmulPlan:
        return _cached_plan(self, m, n, k, act_bytes,
                            weight_bytes if weight_bytes is not None
                            else act_bytes, regime)

    def plan_fc(self, b: int, n: int, k: int, *, act_bytes: int,
                weight_bytes: int | None = None,
                regime: str | None = None) -> FCPlan:
        """Batch-amortized SA-FC planning under this policy's chip/VMEM
        budget — the FC twin of :meth:`plan`: the resident batch tile is
        the weight-amortization lever, the weight stream is charged once
        per batch tile, and the memory-bound -> compute-bound flip batch
        is a plan output (:attr:`~repro.core.dataflow.FCPlan.flip_batch`).
        """
        return _cached_fc_plan(self, b, n, k, act_bytes,
                               weight_bytes if weight_bytes is not None
                               else act_bytes, regime)

    @property
    def effective_vmem_budget(self) -> int:
        """The on-chip allowance every plan under this policy honors."""
        return self.vmem_budget if self.vmem_budget is not None \
            else self.chip.vmem_budget

    def conv_regime_for(self, name: str, batch: int, h: int, w: int,
                        ci: int, p: int, q: int, co: int, stride: int, *,
                        act_bytes: int,
                        weight_bytes: int | None = None) -> str:
        """Conv twin of :meth:`regime_for`: same override/force precedence,
        but the intensity fallback costs *real NHWC bytes* (not the
        patch-matrix GEMM view, which would tag compute-bound convs as
        bandwidth-bound)."""
        for pat, reg in self.overrides:
            if name == pat:
                return reg
        if self.force_regime is not None:
            return self.force_regime
        return dataflow.classify_conv_regime(
            batch, h, w, ci, p, q, co, stride=stride, bytes_in=act_bytes,
            bytes_w=weight_bytes, chip=self.chip)

    def plan_conv(self, batch: int, h: int, w: int, ci: int,
                  p: int, q: int, co: int, stride: int, *, act_bytes: int,
                  weight_bytes: int | None = None,
                  regime: str | None = None,
                  pool: PoolSpec | None = None,
                  act: str = "none") -> ConvPlan:
        """Conv-aware planning under this policy's chip/VMEM budget —
        the CONV twin of :meth:`plan` (traffic counted in real NHWC bytes,
        not patch-matrix bytes).  ``pool`` requests the fused
        maxpool+activation flush epilogue; the planner may decline
        (``fuse_pool=False`` on the returned plan)."""
        return _cached_conv_plan(self, batch, h, w, ci, p, q, co, stride,
                                 act_bytes,
                                 weight_bytes if weight_bytes is not None
                                 else act_bytes, regime, pool, act)


@functools.lru_cache(maxsize=4096)
def _cached_plan(policy: DispatchPolicy, m: int, n: int, k: int,
                 act_bytes: int, weight_bytes: int,
                 regime: str | None) -> MatmulPlan:
    return dataflow.plan_matmul(
        m, n, k, bytes_in=act_bytes, bytes_w=weight_bytes,
        vmem_budget=policy.vmem_budget, chip=policy.chip, regime=regime)


@functools.lru_cache(maxsize=4096)
def _cached_fc_plan(policy: DispatchPolicy, b: int, n: int, k: int,
                    act_bytes: int, weight_bytes: int,
                    regime: str | None) -> FCPlan:
    return dataflow.plan_fc(
        b, n, k, bytes_in=act_bytes, bytes_w=weight_bytes,
        vmem_budget=policy.vmem_budget, chip=policy.chip, regime=regime)


@functools.lru_cache(maxsize=4096)
def _cached_conv_plan(policy: DispatchPolicy, batch: int, h: int, w: int,
                      ci: int, p: int, q: int, co: int, stride: int,
                      act_bytes: int, weight_bytes: int,
                      regime: str | None,
                      pool: PoolSpec | None, act: str) -> ConvPlan:
    return dataflow.plan_conv(
        batch, h, w, ci, p, q, co, stride=stride, bytes_in=act_bytes,
        bytes_w=weight_bytes, vmem_budget=policy.vmem_budget,
        chip=policy.chip, regime=regime, pool=pool, act=act)


# ---------------------------------------------------------------------------
# pallas-path autodiff: custom VJP whose backward matmuls also go through
# the same kernels (dx = g w^T is itself in-regime; in decode it stays
# sa_fc).  Bias-less ops get a structurally bias-less VJP — no sentinel
# zero-bias argument and no fabricated scalar tangent.
# ---------------------------------------------------------------------------
def _pallas_matmul(x2d, w, bias, act, regime, *,
                   plan=None, w_scale=None, out_dtype=None,
                   vmem_limit=None):
    if regime == "sa_fc":
        bb, bn, bk = None, 512, 512
        if isinstance(plan, FCPlan):
            # planner tiles are pre-capped at dataflow.MAX_TILE: executed
            # block shapes equal the plan's (no silent clamp drift), and
            # the resident batch tile is the plan's amortization decision
            bb, bn, bk = plan.bb, plan.bn, plan.bk
        elif plan is not None:
            bn, bk = plan.bn, plan.bk
        return sa_fc_matmul(x2d, w, bias, act=act, bb=bb, bn=bn, bk=bk,
                            w_scale=w_scale, out_dtype=out_dtype,
                            vmem_limit=vmem_limit)
    if isinstance(plan, FCPlan):
        plan = None                  # sa_conv kernel plans its own tiling
    return sa_conv_matmul(x2d, w, bias, act=act, plan=plan,
                          w_scale=w_scale, out_dtype=out_dtype,
                          vmem_limit=vmem_limit)


def _fc_dx_plan(b, n_out, k_con, x_dtype, w_dtype, vmem_limit):
    """Batch-tiled plan for the backward ``dx = g @ w^T`` stream: the
    transposed weight matrix is re-streamed once per resident batch tile
    under the same modeled VMEM budget as the forward, so the residency
    invariant (no block that could never be on-chip) holds for both
    passes — not just the forward."""
    return dataflow.plan_fc(b, n_out, k_con,
                            bytes_in=jnp.dtype(x_dtype).itemsize,
                            bytes_w=jnp.dtype(w_dtype).itemsize,
                            vmem_budget=vmem_limit, regime="sa_fc")


def _act_grad(pre, act):
    if act == "none":
        return jnp.ones_like(pre)
    return jax.vjp(lambda t: ref.apply_act(t, act), pre)[1](
        jnp.ones_like(pre))[0]


@functools.lru_cache(maxsize=256)
def _make_pallas_vjp(act: str, regime: str,
                     has_bias: bool, out_dtype,
                     plan, vmem_limit: int | None = None):
    def _bwd_core(x2d, w, bias, g):
        pre = _pallas_matmul(x2d, w, bias, "none", regime, plan=plan,
                             vmem_limit=vmem_limit).astype(jnp.float32)
        dpre = (g.astype(jnp.float32) * _act_grad(pre, act)).astype(x2d.dtype)
        dx_plan = None
        if regime == "sa_fc":
            # dx = dpre (b, n) @ w.T (n, k): plan the transposed stream
            dx_plan = _fc_dx_plan(x2d.shape[0], w.shape[0], w.shape[1],
                                  x2d.dtype, w.dtype, vmem_limit)
        dx = _pallas_matmul(dpre, w.T, None, "none", regime,
                            plan=dx_plan, vmem_limit=vmem_limit)
        dw = _pallas_matmul(x2d.T, dpre, None, "none", "sa_conv",
                            vmem_limit=vmem_limit)
        return dpre, dx, dw.astype(w.dtype)

    if has_bias:
        @jax.custom_vjp
        def f(x2d, w, bias):
            return _pallas_matmul(x2d, w, bias, act, regime,
                                  plan=plan, out_dtype=out_dtype,
                                  vmem_limit=vmem_limit)

        def fwd(x2d, w, bias):
            return f(x2d, w, bias), (x2d, w, bias)

        def bwd(res, g):
            x2d, w, bias = res
            dpre, dx, dw = _bwd_core(x2d, w, bias, g)
            db = jnp.sum(dpre.astype(jnp.float32), axis=0).astype(bias.dtype)
            return dx, dw, db
    else:
        @jax.custom_vjp
        def f(x2d, w):
            return _pallas_matmul(x2d, w, None, act, regime,
                                  plan=plan, out_dtype=out_dtype,
                                  vmem_limit=vmem_limit)

        def fwd(x2d, w):
            return f(x2d, w), (x2d, w)

        def bwd(res, g):
            x2d, w = res
            _, dx, dw = _bwd_core(x2d, w, None, g)
            return dx, dw

    f.defvjp(fwd, bwd)
    return f


def _quantized_pallas_matmul(x2d, wq, w_scale, bias, act, regime,
                             plan, out_dtype, vmem_limit=None):
    """Quantized pallas matmul, differentiable in ``x`` (and ``bias``).

    The int8 weights + scale are closed over as constants: no weight
    tangent (frozen quantized weights), and the backward pass streams the
    transposed int8 matrix through the same kernels — dx = (g*act') with
    the per-column scale folded in, dotted against q^T, so backward HBM
    weight traffic is also 1 byte/weight."""
    has_bias = bias is not None

    def pre_fn(xv, bv):
        return _pallas_matmul(xv, wq, bv, "none", regime,
                              plan=plan, w_scale=w_scale,
                              vmem_limit=vmem_limit)

    @jax.custom_vjp
    def f(xv, bv):
        return _pallas_matmul(xv, wq, bv if has_bias else None, act, regime,
                              plan=plan, w_scale=w_scale,
                              out_dtype=out_dtype, vmem_limit=vmem_limit)

    def fwd(xv, bv):
        return f(xv, bv), (xv, bv)

    def bwd(res, g):
        xv, bv = res
        pre = pre_fn(xv, bv if has_bias else None).astype(jnp.float32)
        dpre = g.astype(jnp.float32) * _act_grad(pre, act)
        # fold the per-output-channel scale into the cotangent, then dot
        # against the raw int8 transpose (widened on-chip by the kernel)
        dscaled = (dpre * w_scale.astype(jnp.float32)).astype(xv.dtype)
        dx_plan = None
        if regime == "sa_fc":
            dx_plan = _fc_dx_plan(xv.shape[0], wq.shape[0], wq.shape[1],
                                  xv.dtype, wq.dtype, vmem_limit)
        dx = _pallas_matmul(dscaled, wq.T, None, "none", regime,
                            plan=dx_plan, vmem_limit=vmem_limit)
        if has_bias:
            db = jnp.sum(dpre, axis=0).astype(bv.dtype)
            return dx, db
        return dx, None

    f.defvjp(fwd, bwd)
    return f(x2d, bias if has_bias else None)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
_TRACE_UNSET = object()     # distinguishes "no per-thread trace" from None


class Engine:
    """Explicit execution engine: configuration + policy + trace + schedule.

    Construct one per deployment (or phase) and either call its methods
    directly or :meth:`activate` it so model code reaching the module-level
    shims resolves to it::

        eng = Engine(backend="pallas")
        with eng.tracing() as tr:
            y = eng.matmul(x, w, act="relu", name="fc1")
        print(tr.summary())

    Attach a compiled :class:`~repro.core.schedule.LayerSchedule` with
    :meth:`with_schedule` and every named op resolves its
    :class:`~repro.core.dataflow.MatmulPlan` by lookup instead of
    re-planning at trace time (recorded as ``schedule="hit"``).

    The pallas backend's kernels run compiled on a TPU and in the Pallas
    interpreter elsewhere; the platform decides
    (:func:`repro.kernels.geometry.interpret_mode`), never the caller.
    Plans are made for the chip the process runs on
    (:func:`repro.core.accelerator.current_chip`) unless ``chip`` or
    ``policy`` names one.
    """

    def __init__(self, *, backend: str = "xla",
                 chip: TPUChip | None = None,
                 policy: DispatchPolicy | None = None,
                 schedule: Any | None = None,
                 trace: DispatchTrace | None = None,
                 verify_schedules: bool = False) -> None:
        if policy is None:
            policy = DispatchPolicy(chip=chip if chip is not None
                                    else current_chip())
        elif chip is not None and chip is not policy.chip:
            policy = dataclasses.replace(policy, chip=chip)
        self.policy = policy
        self.backend = backend
        self.schedule = schedule
        # debug hook: statically verify any schedule at attach time (and
        # through with_schedule, which round-trips this flag via with_)
        self.verify_schedules = verify_schedules
        if verify_schedules and schedule is not None:
            from repro.analysis import verify_schedule
            verify_schedule(schedule).raise_if_failed()
        # constructor-supplied trace is shared across threads (derived
        # engines); tracing() overlays a per-thread trace on top so
        # concurrent tracing() users of one engine stay isolated, like the
        # old thread-local engine state
        self._trace_default = trace
        self._trace_tls = threading.local()

    @property
    def trace(self) -> DispatchTrace | None:
        tls = getattr(self._trace_tls, "trace", _TRACE_UNSET)
        return self._trace_default if tls is _TRACE_UNSET else tls

    @trace.setter
    def trace(self, tr: DispatchTrace | None) -> None:
        self._trace_tls.trace = tr

    @property
    def chip(self) -> TPUChip:
        return self.policy.chip

    # -- derivation ---------------------------------------------------------
    def with_(self, **overrides: Any) -> Engine:
        """A derived engine sharing this engine's live trace."""
        kw = dict(backend=self.backend, policy=self.policy,
                  schedule=self.schedule, trace=self.trace,
                  verify_schedules=self.verify_schedules)
        kw.update(overrides)
        return Engine(**kw)

    def with_schedule(self, schedule) -> Engine:
        return self.with_(schedule=schedule)

    # -- context ------------------------------------------------------------
    @contextlib.contextmanager
    def activate(self):
        """Make this the engine that module-level shims resolve to."""
        stack = _engine_stack()
        stack.append(self)
        try:
            yield self
        finally:
            stack.pop()

    @contextlib.contextmanager
    def tracing(self):
        """Collect dispatch records into a fresh :class:`DispatchTrace`.
        Per-thread: concurrent ``tracing()`` entries on a shared engine do
        not see each other's records."""
        prev = getattr(self._trace_tls, "trace", _TRACE_UNSET)
        tr = DispatchTrace()
        self._trace_tls.trace = tr
        try:
            yield tr
        finally:
            if prev is _TRACE_UNSET:
                del self._trace_tls.trace
            else:
                self._trace_tls.trace = prev

    @contextlib.contextmanager
    def tagging(self, *, stage: str = "", wave: int = -1):
        """Tag every record issued inside the context with the pipeline
        stage ('conv' | 'fc') and serving wave that dispatched it — the
        dual-array serving pipeline's provenance labels.  Per-thread and
        re-entrant, like :meth:`tracing`."""
        prev = getattr(self._trace_tls, "tags", None)
        self._trace_tls.tags = (stage, wave)
        try:
            yield self
        finally:
            self._trace_tls.tags = prev

    def record(self, **kw: Any) -> None:
        """Append a :class:`DispatchRecord` to the live trace (no-op when
        not tracing).  Public for ops that execute outside ``matmul`` /
        ``attention`` but still belong in the dispatch picture (e.g. the
        MoE per-expert einsums)."""
        if self.trace is not None:
            tags = getattr(self._trace_tls, "tags", None)
            if tags is not None:
                kw.setdefault("stage", tags[0])
                kw.setdefault("wave", tags[1])
            self.trace.append(DispatchRecord(**kw))

    # internal alias
    _record = record

    # -- planning -----------------------------------------------------------
    def plan_for(self, name: str, m: int, n: int, k: int, *,
                 dtype, weight_dtype) -> tuple[Any, str]:
        """(plan, 'hit'|'miss'|'') for one named op — schedule lookup with
        policy fallback.  Ops assigned to the SA-FC array get a
        batch-amortized :class:`~repro.core.dataflow.FCPlan` (the resident
        batch tile is the weight-amortization lever); SA-CONV ops get a
        :class:`~repro.core.dataflow.MatmulPlan` as before."""
        act_bytes = jnp.dtype(dtype).itemsize
        w_bytes = jnp.dtype(weight_dtype).itemsize
        state = ""
        if self.schedule is not None:
            plan = self.schedule.lookup(name, m, n, k, str(jnp.dtype(dtype)),
                                        str(jnp.dtype(weight_dtype)))
            if plan is not None:
                return plan, "hit"
            state = "miss"
        regime = self.policy.regime_for(name, m, n, k, act_bytes=act_bytes,
                                        weight_bytes=w_bytes)
        try:
            if regime == "sa_fc":
                plan = self.policy.plan_fc(m, n, k, act_bytes=act_bytes,
                                           weight_bytes=w_bytes,
                                           regime=regime)
            else:
                plan = self.policy.plan(m, n, k, act_bytes=act_bytes,
                                        weight_bytes=w_bytes, regime=regime)
        except dataflow.PlanError as e:
            # the planner knows the shape/budget; the engine knows which
            # layer asked — surface both in one typed error
            raise e.with_op(name) from e
        return plan, state

    def plan_conv_for(self, name: str, batch: int, h: int, w: int, ci: int,
                      p: int, q: int, co: int, stride: int, *,
                      dtype, weight_dtype,
                      pool: PoolSpec | None = None,
                      act: str = "none") -> tuple[ConvPlan, str]:
        """(conv plan, 'hit'|'miss'|'') for one named CONV op — schedule
        lookup with policy fallback.  ``h``/``w`` are the padded input
        spatial dims; ``pool`` is the maxpool stage requested to ride the
        flush epilogue (the plan's ``fuse_pool`` records the decision)."""
        act_bytes = jnp.dtype(dtype).itemsize
        w_bytes = jnp.dtype(weight_dtype).itemsize
        state = ""
        if self.schedule is not None:
            plan = self.schedule.lookup_conv(
                name, batch, h, w, ci, p, q, co, stride,
                str(jnp.dtype(dtype)), str(jnp.dtype(weight_dtype)),
                pool=pool)
            if plan is not None:
                return plan, "hit"
            state = "miss"
        regime = self.policy.conv_regime_for(name, batch, h, w, ci, p, q,
                                             co, stride,
                                             act_bytes=act_bytes,
                                             weight_bytes=w_bytes)
        try:
            plan = self.policy.plan_conv(batch, h, w, ci, p, q, co, stride,
                                         act_bytes=act_bytes,
                                         weight_bytes=w_bytes, regime=regime,
                                         pool=pool, act=act)
        except dataflow.PlanError as e:
            raise e.with_op(name) from e
        return plan, state

    # -- ops ----------------------------------------------------------------
    def matmul(self, x: jax.Array, w, bias: jax.Array | None = None, *,
               act: str = "none", name: str = "matmul",
               out_dtype=None) -> jax.Array:
        """``(..., k) @ (k, n)`` with fused bias+activation epilogue, routed
        to the SA-CONV or SA-FC dataflow by the engine's policy/schedule.

        ``w`` may be a :class:`repro.core.quant.QTensor` (int8 + per-channel
        scales — the paper's 8-bit fixed point): the int8 weights reach the
        kernel un-dequantized and the per-channel scale fuses into the
        accumulator-flush epilogue, so HBM moves 1 byte/weight."""
        from repro.core.quant import QTensor
        if isinstance(w, QTensor):
            wq, w_scale = w.q, w.scale.reshape(1, -1)
        else:
            wq, w_scale = w, None
        *lead, k = x.shape
        n = wq.shape[-1]
        m = 1
        for s in lead:
            m *= s
        plan, sched = self.plan_for(name, m, n, k, dtype=x.dtype,
                                    weight_dtype=wq.dtype)
        is_fc = isinstance(plan, dataflow.FCPlan)
        self._record(name=name, regime=plan.regime, m=m, n=n, k=k,
                     case=plan.case, backend=self.backend,
                     dtype=str(x.dtype), weight_dtype=str(wq.dtype),
                     schedule=sched, plan=None if is_fc else plan,
                     fc_plan=plan if is_fc else None)

        x2d = x.reshape(m, k)
        out_dt = jnp.dtype(out_dtype) if out_dtype is not None else x.dtype
        if self.backend == "pallas":
            vmem_limit = self.policy.effective_vmem_budget
            if w_scale is not None:
                # frozen quantized weights: differentiable in x/bias only
                out = _quantized_pallas_matmul(x2d, wq, w_scale, bias, act,
                                               plan.regime, plan, out_dt,
                                               vmem_limit)
            elif bias is not None:
                fn = _make_pallas_vjp(act, plan.regime, True, out_dt, plan,
                                      vmem_limit)
                out = fn(x2d, wq, bias)
            else:
                fn = _make_pallas_vjp(act, plan.regime, False, out_dt, plan,
                                      vmem_limit)
                out = fn(x2d, wq)
        else:
            out = ref.matmul_bias_act(x2d, wq, bias, act=act,
                                      out_dtype=out_dt, w_scale=w_scale)
        # dtype was applied exactly once (kernel epilogue / oracle); the
        # reshape below must not re-cast.
        return out.reshape(*lead, n)

    def conv2d(self, x: jax.Array, f, bias: jax.Array | None = None, *,
               stride: int = 1, pad: int = 0, act: str = "none",
               pool: PoolSpec | None = None,
               name: str = "conv", out_dtype=None) -> jax.Array:
        """NHWC x HWIO convolution with fused bias+activation epilogue,
        planned by the engine's policy/schedule and executed on the
        implicit-GEMM SA-CONV kernel (``backend="pallas"``) or the XLA
        oracle.  No im2col patch matrix is ever materialized in HBM.

        ``f`` may be a :class:`repro.core.quant.QTensor` (int8 + per-output-
        channel scales): the int8 filter reaches the kernel un-dequantized
        and the scale fuses into the accumulator-flush epilogue.

        ``pool`` requests the following maxpool stage to ride the same
        epilogue (the paper's pooling-&-activation unit after accumulation,
        Fig. 7): semantics are ``maxpool(act(conv(x) + bias))``.  The
        *planner* owns the decision — when the plan accepts
        (``conv_plan.fuse_pool`` in the trace) the kernel emits the pooled
        map directly and the full OFM never crosses HBM; when it declines
        (non-monotone ``act``, pool windows that don't tile the OFM, VMEM
        budget overflow) the conv runs unfused and the pool is dispatched
        as a standalone :meth:`pool` pass (visible in the trace as
        ``<name>.pool``).

        ``plan.regime`` names the *array* the schedule assigns the layer
        to — the paper runs CONV on both arrays (SA-FC is CONV-capable,
        Sec. IV-B) — so a forced/overridden regime changes the planning
        and the trace accounting, not the kernel: the implicit-GEMM
        kernel is the single CONV implementation for either assignment."""
        from repro.core.quant import QTensor
        if isinstance(f, QTensor):
            fq, f_scale = f.q, f.scale.reshape(-1)
        else:
            fq, f_scale = f, None
        if pad:
            x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        batch, h, w, ci = x.shape
        p, q, ci2, co = fq.shape
        assert ci == ci2, (x.shape, fq.shape)
        plan, sched = self.plan_conv_for(name, batch, h, w, ci, p, q, co,
                                         stride, dtype=x.dtype,
                                         weight_dtype=fq.dtype,
                                         pool=pool, act=act)
        self._record(name=name, regime=plan.regime, m=plan.m, n=plan.n,
                     k=plan.k, case=plan.case, backend=self.backend,
                     dtype=str(x.dtype), weight_dtype=str(fq.dtype),
                     schedule=sched, conv_plan=plan,
                     conv_shape=(batch, h, w, ci, p, q, co, stride),
                     pool=pool)
        out_dt = jnp.dtype(out_dtype) if out_dtype is not None else x.dtype
        if self.backend == "pallas":
            out = sa_conv_implicit(
                x, fq, bias, stride=stride, act=act, plan=plan,
                w_scale=f_scale, out_dtype=out_dt,
                vmem_limit=self.policy.effective_vmem_budget)
        else:
            ff = fq if f_scale is None else \
                (fq.astype(jnp.float32) * f_scale.reshape(1, 1, 1, co))
            out = ref.conv2d(x, ff, stride=stride, out_dtype=jnp.float32)
            if bias is not None:
                out = out + bias.astype(jnp.float32)
            out = ref.apply_act(out, act).astype(out_dt)
            if plan.fuse_pool:
                out = ref.maxpool2d(out, window=plan.pool_window,
                                    stride=plan.pool_stride)
        if pool is not None and not plan.fuse_pool:
            # planner declined: run the paper's standalone pooling unit,
            # dispatched (and traced) in its own right
            out = self.pool(out, window=pool.window, stride=pool.stride,
                            name=f"{name}.pool")
        return out

    def pool(self, x: jax.Array, *, window: int, stride: int | None = None,
             act: str = "none", name: str = "pool") -> jax.Array:
        """Standalone maxpool + activation (the paper's pooling-&-activation
        unit as its own dispatch): recorded in the trace like every other
        op instead of bypassing the engine.  Unfused pool layers and
        declined conv+pool fusions route here."""
        stride = stride if stride is not None else window
        n, h, w, c = x.shape
        oh = (h - window) // stride + 1
        ow = (w - window) // stride + 1
        self._record(name=name, regime="pool", m=n * oh * ow, n=c,
                     k=window * window, case=0, backend=self.backend,
                     dtype=str(x.dtype), pool=PoolSpec(window, stride))
        if self.backend == "pallas":
            return maxpool_act(x, window=window, stride=stride, act=act,
                               vmem_limit=self.policy.effective_vmem_budget)
        return ref.maxpool_act(x, window=window, stride=stride, act=act)

    def attention(self, q, k, v, *, causal=True, window=0, softcap=0.0,
                  scale=None, name="attn"):
        """Blocked attention; pallas flash kernel or the jnp oracle."""
        self._record(name=name, regime="attention", m=q.shape[1],
                     n=k.shape[1], k=q.shape[-1], case=0,
                     backend=self.backend, dtype=str(q.dtype))
        if self.backend == "pallas":
            from repro.kernels.attention import flash_attention
            return flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, scale=scale)
        return ref.attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale)

    def __repr__(self) -> str:
        return (f"Engine(backend={self.backend!r}, "
                f"policy={self.policy}, "
                f"schedule={'yes' if self.schedule is not None else 'no'})")


# ---------------------------------------------------------------------------
# current-engine stack (explicit successor of the old hidden _STATE)
# ---------------------------------------------------------------------------
_LOCAL = threading.local()
_DEFAULT: Engine | None = None


def _engine_stack() -> list[Engine]:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


def current() -> Engine:
    """The innermost :meth:`Engine.activate`-d engine, else the module
    default (xla backend, default policy)."""
    stack = _engine_stack()
    return stack[-1] if stack else default_engine()


def default_engine() -> Engine:
    """The module default engine, built on first use (building it asks
    which chip the process runs on, which importing must not do)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Engine()
    return _DEFAULT


# ---------------------------------------------------------------------------
# deprecation shims (legacy module-level API)
# ---------------------------------------------------------------------------
def matmul(x: jax.Array, w, bias: jax.Array | None = None, *,
           act: str = "none", name: str = "matmul",
           out_dtype=None) -> jax.Array:
    """Deprecated shim: ``current().matmul(...)``."""
    return current().matmul(x, w, bias, act=act, name=name,
                            out_dtype=out_dtype)


def conv2d(x: jax.Array, f, bias: jax.Array | None = None, *,
           stride: int = 1, pad: int = 0, act: str = "none",
           pool: PoolSpec | None = None,
           name: str = "conv", out_dtype=None) -> jax.Array:
    """Deprecated shim: ``current().conv2d(...)``."""
    return current().conv2d(x, f, bias, stride=stride, pad=pad, act=act,
                            pool=pool, name=name, out_dtype=out_dtype)


def attention(q, k, v, *, causal=True, window=0, softcap=0.0,
              scale=None, name="attn"):
    """Deprecated shim: ``current().attention(...)``."""
    return current().attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale, name=name)


@contextlib.contextmanager
def execution(backend: str = "xla"):
    """Deprecated shim: activate a derived engine with this backend.
    Prefer constructing an :class:`Engine` and calling its methods."""
    eng = current().with_(backend=backend)
    with eng.activate():
        yield eng


@contextlib.contextmanager
def dispatch_trace():
    """Deprecated shim: collect dispatch records from ops issued inside the
    context.  Prefer ``with engine.tracing() as tr``.

    Activates a *derived* engine carrying a fresh trace rather than
    mutating the shared default — the activation stack is thread-local, so
    concurrent shim users stay isolated (the old ``_EngineState``
    thread-local guarantee)."""
    tr = DispatchTrace()
    with current().with_(trace=tr).activate():
        yield tr
