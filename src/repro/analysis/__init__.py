"""Static analysis of compiled schedules and kernel launch geometry.

This package verifies — **without executing any kernel** — that every
plan a :class:`~repro.core.schedule.LayerSchedule` carries is exactly
what the Pallas kernels would launch and exactly what the paper's
traffic/residency model claims.  Schedules are compiled shape-only
(``jax.eval_shape``); the verifier walks integer grids and re-derives
every byte count from first principles, independently of the planner's
own arithmetic, so a planner/kernel drift bug cannot certify itself.

Invariants checked, by pass:

``coverage`` — grid-coverage & tile lint
    * every plan tile (``bb``/``bm``/``bn``/``bk``/``bi``/``bj``) is
      positive and at most :data:`~repro.core.dataflow.MAX_TILE`;
    * GEMM row tiles are SUBLANE(16)-aligned, column/contraction tiles
      LANE(128)-aligned; conv channel tiles are SUBLANE-aligned or
      equal to the exact channel count (the padding-free RGB stem);
    * the plan's tiles equal the kernel's normalized tiles
      (:func:`~repro.kernels.geometry.fc_normalize` — no silent clamp
      drift between planner and kernel) and the kernel grid equals the
      plan's grid;
    * symbolically evaluating every operand's index map over the whole
      grid visits a contiguous, Cartesian-complete set of block indices
      whose blocks cover the operand's full logical extent (no coverage
      gap, no silently clamped tail); an element-indexed operand (a conv
      band's overlapping input window) instead starts at 0, leaves no
      gap between windows and reaches the extent;
    * a conv planned in row bands: the bands tile the output rows, each
      input window holds its band plus the ``p' - 1`` halo rows in
      phase space and starts one band below the last, and a fused pool
      stays inside one band (window == stride, band a multiple of it).

``residency`` — VMEM residency sanitizer
    * the resident working set re-derived from the block specs alone
      (double-buffered inputs, fp32 accumulator scratch, the pooled or
      full output tile; conv blocks in the chip's (sublane, 128) tiles,
      with the patch-row / tap-streaming temporaries) equals the plan's
      ``vmem_bytes`` byte-for-byte;
    * it fits the policy's effective VMEM budget (the conv planner
      raises ``PlanError`` instead of planning over it).

``race`` — grid write-race detector
    * the reduction ("arbitrary") grid dimensions form the
      innermost-sequential suffix of the grid;
    * no two grid steps write the same output block while differing in a
      "parallel" dimension (symbolic evaluation of the output index map
      over the grid).

``accounting`` — byte-accounting lint
    * ``hbm_bytes`` equals an independent replica of the planner's
      traffic model at the plan's tiles, and is never below the
      compulsory (every-byte-once) minimum;
    * FC: ``weight_hbm_bytes`` equals streamed-passes x padded weight
      bytes, ``weight_passes`` matches, ``flip_batch`` matches the
      closed form AND :func:`~repro.core.dataflow.classify_regime`'s
      verdict at the flip (and one sample before it);
    * conv: the GEMM view ``m/n/k`` and ``flops`` are consistent with
      the layer geometry, fused-pool byte credits are non-negative, a
      fused pool tiles the OFM it claims to pool, and the plan's regime
      matches the policy's classification;
    * a policy-classified SA-FC op must carry a batch-amortized
      :class:`~repro.core.dataflow.FCPlan`, never a bare MatmulPlan.

``determinism`` — scheduler-determinism lint (AST, source-level)
    * the modeled-virtual-time code paths (``serve/zoo.py``,
      ``serve/cnn_server.py``, ``benchmarks/timing.py``) contain no
      wall-clock reads, no unseeded randomness (stdlib ``random``,
      ``np.random.*`` without an explicit seed, ``os.urandom``,
      ``uuid4``) and no iteration over unordered sets — with per-file
      exemptions for the wall-clock measurement utilities themselves
      and an inline ``# det: allow`` pragma.

Entry points: :func:`verify_schedule` / :func:`verify_registry` (and the
``python -m repro.analysis`` CLI, which also mirrors the zoo's exact
registration path for ``--all-zoo-variants``).  Debug hooks:
``ScheduleRegistry(verify=True)`` and ``Engine(verify_schedules=True)``
verify every schedule at compile/attach time and raise
:class:`ScheduleVerificationError` on the first violation.
"""
from repro.analysis.determinism import lint_scheduler_sources
from repro.analysis.passes import OpContext, SCHEDULE_PASSES
from repro.analysis.report import (
    PASSES,
    AnalysisReport,
    Finding,
    ScheduleVerificationError,
    merge_reports,
)
from repro.analysis.verify import (
    context_for,
    verify_context,
    verify_registry,
    verify_schedule,
    verify_stage_pair,
)

__all__ = [
    "PASSES",
    "SCHEDULE_PASSES",
    "AnalysisReport",
    "Finding",
    "OpContext",
    "ScheduleVerificationError",
    "context_for",
    "lint_scheduler_sources",
    "merge_reports",
    "verify_context",
    "verify_registry",
    "verify_schedule",
    "verify_stage_pair",
]
