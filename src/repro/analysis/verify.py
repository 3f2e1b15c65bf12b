"""Schedule verification drivers: build an :class:`OpContext` for every
entry of a compiled :class:`~repro.core.schedule.LayerSchedule` and run
the four geometry/accounting passes over it.

The geometry each context carries comes from the same
:mod:`repro.kernels.geometry` builders the kernels launch from, so a
clean report means the *actual* launch shapes — not a parallel model of
them — cover the op, stay VMEM-resident, and are race-free.  No kernel
is ever executed: schedules are compiled with ``jax.eval_shape`` and
the passes only walk integer grids.
"""
from __future__ import annotations

from repro.analysis.determinism import lint_scheduler_sources
from repro.analysis.passes import SCHEDULE_PASSES, OpContext
from repro.analysis.report import AnalysisReport, Finding, merge_reports
from repro.core.dataflow import ConvPlan, FCPlan, MatmulPlan, conv_phase_dims
from repro.core.schedule import ConvOpKey, LayerSchedule, OpKey
from repro.kernels.geometry import (
    conv_geometry,
    fc_geometry,
    matmul_geometry,
)


def context_for(key: OpKey | ConvOpKey,
                plan: FCPlan | MatmulPlan | ConvPlan,
                policy) -> OpContext:
    """The verification context of one schedule entry: its launch
    geometry (built exactly as the kernel would) plus the logical
    operand extents the grid must cover.  Scale/bias operands are
    included unconditionally — verifying a superset of the launch is
    sound, and it keeps their row index maps covered too."""
    if isinstance(plan, ConvPlan):
        k: ConvOpKey = key
        geom = conv_geometry(k.batch, k.h, k.w, k.ci, k.p, k.q, k.co,
                             stride=k.stride, plan=plan,
                             has_scale=True, has_bias=True)
        oh = (k.h - k.p) // k.stride + 1
        ow = (k.w - k.q) // k.stride + 1
        ooh, oow = oh, ow
        if plan.fuse_pool:
            ooh = (oh - plan.pool_window) // plan.pool_stride + 1
            oow = (ow - plan.pool_window) // plan.pool_stride + 1
        # a strided conv launches over its phase-split operands
        hh, ww, pp, qq = conv_phase_dims(k.h, k.w, k.p, k.q, k.stride)
        pci = k.stride * k.stride * k.ci
        extents = {"x": (k.batch, hh, ww, pci),
                   "w": (pp, qq, pci, k.co),
                   "scale": (1, k.co), "bias": (1, k.co),
                   "out": (k.batch, ooh, oow, k.co)}
        return OpContext(op=f"{k.name} [conv]", kind="conv", key=key,
                         plan=plan, geom=geom, extents=extents,
                         policy=policy)
    row_extents = {"x": (key.m, key.k), "w": (key.k, key.n),
                   "scale": (1, key.n), "bias": (1, key.n),
                   "out": (key.m, key.n)}
    if isinstance(plan, FCPlan):
        geom = fc_geometry(plan.b, plan.n, plan.k, bb=plan.bb,
                           bn=plan.bn, bk=plan.bk,
                           has_scale=True, has_bias=True)
        return OpContext(op=f"{key.name} [sa_fc]", kind="fc", key=key,
                         plan=plan, geom=geom, extents=row_extents,
                         policy=policy)
    geom = matmul_geometry(key.m, key.n, key.k, bm=plan.bm, bn=plan.bn,
                           bk=plan.bk, has_scale=True, has_bias=True)
    return OpContext(op=f"{key.name} [sa_conv]", kind="matmul", key=key,
                     plan=plan, geom=geom, extents=row_extents,
                     policy=policy)


def verify_context(ctx: OpContext) -> list[Finding]:
    """All four schedule passes over one op context."""
    findings: list[Finding] = []
    for check in SCHEDULE_PASSES:
        findings.extend(check(ctx))
    return findings


def verify_schedule(schedule: LayerSchedule, *,
                    label: str = "") -> AnalysisReport:
    """Statically verify every entry (matmul, FC and conv) of one
    compiled schedule against the policy it was compiled under."""
    report = AnalysisReport(label=label or f"schedule:{schedule.phase}")
    for key, plan in schedule.conv_entries.items():
        report.add(verify_context(
            context_for(key, plan, schedule.policy)))
        report.checked_ops += 1
    for key, plan in schedule.items():
        report.add(verify_context(
            context_for(key, plan, schedule.policy)))
        report.checked_ops += 1
    return report


def verify_stage_pair(stages, *, label: str = "") -> AnalysisReport:
    """Verify a (conv-stage, fc-stage) schedule pair — what one
    :meth:`~repro.core.schedule.ScheduleRegistry.register` files."""
    conv_sched, fc_sched = stages
    return merge_reports(label or "stages", [
        verify_schedule(conv_sched, label=f"{label}:conv"),
        verify_schedule(fc_sched, label=f"{label}:fc"),
    ])


def verify_registry(registry, *,
                    with_determinism_lint: bool = False) -> AnalysisReport:
    """Verify every (net, dtype_tag, batch) variant filed in a
    :class:`~repro.core.schedule.ScheduleRegistry`, optionally plus the
    scheduler-determinism lint."""
    reports = [verify_stage_pair(registry.stages(*key),
                                 label=f"{key[0]}/{key[1]}@b{key[2]}")
               for key in registry.keys()]
    if with_determinism_lint:
        reports.append(lint_scheduler_sources())
    return merge_reports("registry", reports)
