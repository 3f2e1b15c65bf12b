"""``python -m repro.analysis`` — the static schedule verifier CLI.

Compiles schedules shape-only (``jax.eval_shape``; zero kernel
execution), runs every verification pass, prints one summary block per
verified variant, and exits nonzero on any error-severity finding —
the CI gate entry point.

Examples::

    python -m repro.analysis --net alexnet
    python -m repro.analysis --net vgg16 --batch 8
    python -m repro.analysis --all-zoo-variants
"""
from __future__ import annotations

import argparse
import sys

from repro.analysis.determinism import lint_scheduler_sources
from repro.analysis.report import AnalysisReport, Finding, merge_reports
from repro.analysis.verify import verify_stage_pair
from repro.core.schedule import ScheduleRegistry


def _verify_named_nets(nets: list[str], batch: int) -> list[AnalysisReport]:
    registry = ScheduleRegistry()
    return [verify_stage_pair(registry.register(net, batch=batch),
                              label=f"{net}@b{batch}")
            for net in nets]


def _verify_zoo_variants(max_batch: int) -> list[AnalysisReport]:
    """Verify every :data:`~repro.configs.registry.ZOO_MODELS` variant,
    registered exactly the way :class:`~repro.serve.zoo.ModelZooServer`
    registers it: abstract (eval_shape) parameter trees, the server's
    planner-preferred micro-batch, the server's engine policy.  The
    int8 variant quantizes its abstract tree so the schedule keys carry
    the real 1-byte weight stream.

    A variant the planner refuses (a layer not even a one-row band fits
    on the chip) has no schedule to verify: it is reported
    as a warning naming the layer, and the variants that plan are still
    verified."""
    import jax

    from repro.configs.registry import ZOO_MODELS
    from repro.core.dataflow import PlanError
    from repro.core.quant import quantize_cnn_params
    from repro.models import cnn
    from repro.serve.cnn_server import CNNServer

    registry = ScheduleRegistry()
    reports = []
    for spec in ZOO_MODELS.values():
        params = jax.eval_shape(
            lambda spec=spec: cnn.init_cnn(spec.net, jax.random.PRNGKey(0),
                                           in_res=spec.in_res))
        if spec.weight_dtype == "int8":
            params = jax.eval_shape(quantize_cnn_params, params)
        try:
            srv = CNNServer(spec.net, params, in_res=spec.in_res,
                            max_batch=max_batch)
        except PlanError as e:
            reports.append(AnalysisReport(label=spec.name, findings=[Finding(
                "residency", f"{spec.name}/{e.op}",
                f"cannot be served on this chip: {e}",
                severity="warning")]))
            continue
        pair = registry.register(
            spec.net, dtype_tag=spec.weight_dtype, batch=srv.microbatch,
            in_res=srv.in_res, in_ch=srv.in_ch,
            width_mult=srv.width_mult, dtype=srv.dtype,
            policy=srv.engine.policy, params=srv.params)
        reports.append(verify_stage_pair(
            pair, label=f"{spec.name}@b{srv.microbatch}"))
    return reports


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Statically verify compiled schedules and kernel "
                    "launch geometry (no kernel execution).")
    ap.add_argument("--net", action="append", default=[],
                    help="verify one network's stage schedules "
                         "(repeatable; e.g. --net alexnet --net vgg16)")
    ap.add_argument("--batch", type=int, default=1,
                    help="batch size for --net schedules (default 1)")
    ap.add_argument("--all-zoo-variants", action="store_true",
                    help="verify every zoo registry variant at its "
                         "planner-preferred micro-batch")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="zoo server admission cap for "
                         "--all-zoo-variants (default 8, the zoo's)")
    ap.add_argument("--skip-determinism-lint", action="store_true",
                    help="skip the scheduler-determinism source lint")
    args = ap.parse_args(argv)

    if not args.net and not args.all_zoo_variants:
        ap.error("nothing to verify: pass --net and/or --all-zoo-variants")

    reports: list[AnalysisReport] = []
    if args.net:
        reports.extend(_verify_named_nets(args.net, args.batch))
    if args.all_zoo_variants:
        reports.extend(_verify_zoo_variants(args.max_batch))
    if not args.skip_determinism_lint:
        reports.append(lint_scheduler_sources())

    for rep in reports:
        print(rep.summary())
    total = merge_reports("repro.analysis", reports)
    print(total.summary())
    return 0 if total.ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
