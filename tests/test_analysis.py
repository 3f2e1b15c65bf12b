"""Static-analysis subsystem tests (``repro.analysis``).

Two halves:

* clean-path: every zoo schedule variant verifies with zero findings and
  zero kernel execution, and the registry/engine debug hooks accept them;
* seeded-mutation self-tests: corrupt one plan field (or one source
  line) at a time and assert the verifier catches exactly that
  corruption with a precise diagnostic — a verifier that cannot fail
  verifies nothing.
"""
import dataclasses
import textwrap

import pytest

from repro.analysis import (
    AnalysisReport,
    Finding,
    ScheduleVerificationError,
    context_for,
    lint_scheduler_sources,
    merge_reports,
    verify_context,
    verify_stage_pair,
)
from repro.analysis.determinism import DEFAULT_TARGETS, lint_file
from repro.analysis.passes import (
    check_accounting,
    check_coverage,
    check_races,
    check_residency,
)
from repro.core.dataflow import MAX_TILE, ConvPlan, FCPlan
from repro.core.engine import Engine
from repro.core.schedule import ScheduleRegistry


# -- shared compiled schedule (memoized; compiled once per process) ----------

@pytest.fixture(scope="module")
def alexnet_pair():
    return ScheduleRegistry().register("alexnet", batch=1)


@pytest.fixture(scope="module")
def fc_ctx(alexnet_pair):
    """Context of one batch-amortized FC entry of the fc stage."""
    _, fc_sched = alexnet_pair
    for key, plan in fc_sched.items():
        if isinstance(plan, FCPlan):
            return context_for(key, plan, fc_sched.policy)
    raise AssertionError("alexnet fc stage holds no FCPlan")


@pytest.fixture(scope="module")
def conv_ctx(alexnet_pair):
    conv_sched, _ = alexnet_pair
    key, plan = next(iter(conv_sched.conv_entries.items()))
    assert isinstance(plan, ConvPlan)
    return context_for(key, plan, conv_sched.policy)


def _mutate(ctx, **plan_fields):
    """Rebuild the context around a plan with one corrupted field."""
    bad_plan = dataclasses.replace(ctx.plan, **plan_fields)
    return context_for(ctx.key, bad_plan, ctx.policy)


def _messages(findings):
    return " | ".join(f.message for f in findings)


# -- clean path --------------------------------------------------------------

def test_alexnet_schedule_verifies_clean(alexnet_pair):
    report = verify_stage_pair(alexnet_pair, label="alexnet@b1")
    assert report.ok, report.summary()
    assert report.checked_ops == 8
    assert report.findings == []


def test_clean_contexts_pass_every_pass(fc_ctx, conv_ctx):
    for ctx in (fc_ctx, conv_ctx):
        assert verify_context(ctx) == []


def test_determinism_lint_clean_on_repo_sources():
    report = lint_scheduler_sources()
    assert report.ok, report.summary()
    assert report.checked_files == len(DEFAULT_TARGETS) == 5


# -- seeded mutations: coverage ----------------------------------------------

def test_coverage_catches_misaligned_batch_tile(fc_ctx):
    findings = check_coverage(_mutate(fc_ctx, bb=24))
    assert findings, "verifier missed a 24-row (non-SUBLANE) batch tile"
    msgs = _messages(findings)
    assert "SUBLANE" in msgs
    assert "normalized tiles" in msgs      # plan-vs-kernel clamp drift


def test_coverage_catches_max_tile_overflow(fc_ctx):
    assert fc_ctx.plan.n >= 2 * MAX_TILE, "pick a wider FC layer"
    findings = check_coverage(_mutate(fc_ctx, bn=2 * MAX_TILE))
    assert any(f"exceeds MAX_TILE={MAX_TILE}" in f.message
               for f in findings), _messages(findings)


def test_coverage_catches_grid_gap(fc_ctx):
    """A grid shrunk below the plan's own grid is both a plan/kernel
    grid disagreement and (on the shrunken axis) a coverage gap."""
    geom = fc_ctx.geom
    shrunk = dataclasses.replace(
        geom, grid=(geom.grid[0], geom.grid[1], geom.grid[2] - 1))
    bad = dataclasses.replace(fc_ctx, geom=shrunk)
    msgs = _messages(check_coverage(bad))
    assert "kernel grid" in msgs and "!= plan grid" in msgs
    assert "silent clamp" in msgs or "coverage gap" in msgs


# -- seeded mutations: residency ---------------------------------------------

def test_residency_catches_vmem_lie(fc_ctx, conv_ctx):
    for ctx in (fc_ctx, conv_ctx):
        findings = check_residency(
            _mutate(ctx, vmem_bytes=ctx.plan.vmem_bytes + 1))
        assert len(findings) == 1
        assert "plan and kernel disagree" in findings[0].message
        assert str(ctx.plan.vmem_bytes + 1) in findings[0].message


# -- seeded mutations: races -------------------------------------------------

def test_race_catches_parallel_reduction_dim(fc_ctx):
    """Re-labelling the FC reduction grid dim 'parallel' makes every
    accumulation step a racing writer of its output block."""
    geom = dataclasses.replace(
        fc_ctx.geom,
        dimension_semantics=("parallel",) * len(fc_ctx.geom.grid))
    findings = check_races(dataclasses.replace(fc_ctx, geom=geom))
    assert any("write race" in f.message for f in findings), \
        _messages(findings)


def test_race_catches_non_innermost_reduction(fc_ctx):
    sem = ("arbitrary",) + ("parallel",) * (len(fc_ctx.geom.grid) - 1)
    geom = dataclasses.replace(fc_ctx.geom, dimension_semantics=sem)
    findings = check_races(dataclasses.replace(fc_ctx, geom=geom))
    assert any("innermost-sequential suffix" in f.message
               for f in findings), _messages(findings)


# -- seeded mutations: accounting --------------------------------------------

def test_accounting_catches_traffic_lie(fc_ctx, conv_ctx):
    for ctx in (fc_ctx, conv_ctx):
        findings = check_accounting(
            _mutate(ctx, hbm_bytes=ctx.plan.hbm_bytes + 64))
        assert any("!= plan.hbm_bytes" in f.message for f in findings), \
            _messages(findings)


def test_accounting_catches_weight_stream_lie(fc_ctx):
    bad = _mutate(fc_ctx,
                  weight_hbm_bytes=fc_ctx.plan.weight_hbm_bytes + 4)
    findings = check_accounting(bad)
    assert any("plan.weight_hbm_bytes" in f.message for f in findings), \
        _messages(findings)


def test_accounting_catches_flip_batch_lie(fc_ctx):
    bad = _mutate(fc_ctx, flip_batch=fc_ctx.plan.flip_batch + 7)
    findings = check_accounting(bad)
    assert any("plan.flip_batch" in f.message for f in findings), \
        _messages(findings)


def test_accounting_catches_bad_case(fc_ctx):
    findings = check_accounting(_mutate(fc_ctx, case=5))
    assert any("outside 1..4" in f.message for f in findings)


def test_accounting_catches_conv_flops_lie(conv_ctx):
    bad = _mutate(conv_ctx, flops=conv_ctx.plan.flops - 2)
    findings = check_accounting(bad)
    assert any("plan.flops" in f.message for f in findings), \
        _messages(findings)


# -- seeded mutations: determinism lint --------------------------------------

def _lint_snippet(tmp_path, source, **kw):
    path = tmp_path / "snippet.py"
    path.write_text(textwrap.dedent(source))
    return lint_file(path, rel="snippet.py", **kw)


def test_determinism_flags_wall_clock(tmp_path):
    findings = _lint_snippet(tmp_path, """\
        import time
        def decide():
            return time.perf_counter()
        """)
    assert len(findings) == 1
    assert "wall-clock call time.perf_counter()" in findings[0].message
    assert findings[0].op == "snippet.py:3"


def test_determinism_pragma_and_exemption(tmp_path):
    source = """\
        import time
        def measure():
            return time.time()
        def decide():
            return time.time()  # det: allow
        """
    assert _lint_snippet(tmp_path, source) != []  # measure() flagged...
    assert _lint_snippet(tmp_path, source,
                         exempt=frozenset({"measure"})) == []


def test_determinism_flags_unseeded_rng_only(tmp_path):
    findings = _lint_snippet(tmp_path, """\
        import numpy as np
        def draw():
            good = np.random.default_rng(1234)
            bad = np.random.default_rng()
            worse = np.random.poisson(3.0)
            return good, bad, worse
        """)
    assert len(findings) == 2
    assert "without a seed" in findings[0].message
    assert "global" in findings[1].message


def test_determinism_flags_set_iteration(tmp_path):
    findings = _lint_snippet(tmp_path, """\
        def order(queues):
            for q in set(queues):
                yield q
            return [x for x in {1, 2}] + list({3, 4})
        """)
    kinds = _messages(findings)
    assert "for-loop over an unordered set" in kinds
    assert "comprehension over an unordered set" in kinds
    assert "list() over an unordered set" in kinds


# -- report / error types ----------------------------------------------------

def test_finding_validates_pass_name_and_severity():
    with pytest.raises(ValueError, match="unknown pass"):
        Finding("typo", "op", "msg")
    with pytest.raises(ValueError, match="severity"):
        Finding("coverage", "op", "msg", severity="fatal")


def test_report_merge_and_raise():
    bad = AnalysisReport(label="b", checked_ops=1)
    bad.findings.append(Finding("residency", "fc1", "working set lie"))
    warn = AnalysisReport(label="w", checked_ops=1)
    warn.findings.append(Finding("coverage", "big", "skipped",
                                 severity="warning"))
    merged = merge_reports("all", [bad, warn])
    assert merged.checked_ops == 2
    assert len(merged.errors) == 1 and len(merged.warnings) == 1
    assert not merged.ok
    with pytest.raises(ScheduleVerificationError,
                       match="working set lie") as ei:
        merged.raise_if_failed()
    assert ei.value.report is merged
    assert warn.ok  # warnings alone do not fail a report
    warn.raise_if_failed()


# -- registry conflict detection + debug hooks -------------------------------

def test_registry_rejects_conflicting_reregistration():
    reg = ScheduleRegistry()
    pair = reg.register("alexnet", batch=1)
    assert reg.register("alexnet", batch=1) is pair  # idempotent
    with pytest.raises(ValueError, match="conflicting re-registration"):
        reg.register("alexnet", batch=1, width_mult=0.5)
    assert len(reg) == 1  # the filed pair survived the rejected call


def test_registry_verify_hook_accepts_clean_schedules(alexnet_pair):
    reg = ScheduleRegistry(verify=True)
    assert reg.register("alexnet", batch=1) == alexnet_pair


class _StubSchedule:
    """Minimal LayerSchedule facade holding one corrupted entry."""
    phase = "fc"

    def __init__(self, ctx):
        self.policy = ctx.policy
        self.conv_entries = {}
        self._entries = {ctx.key: dataclasses.replace(
            ctx.plan, vmem_bytes=ctx.plan.vmem_bytes + 1)}

    def items(self):
        return self._entries.items()


def test_engine_verify_hook(alexnet_pair, fc_ctx):
    _, fc_sched = alexnet_pair
    eng = Engine(backend="pallas", verify_schedules=True)
    derived = eng.with_schedule(fc_sched)        # clean: attaches fine
    assert derived.verify_schedules and derived.schedule is fc_sched
    with pytest.raises(ScheduleVerificationError,
                       match="plan and kernel disagree"):
        eng.with_schedule(_StubSchedule(fc_ctx))
    # the hook is opt-in: a default engine attaches without verifying
    Engine(backend="pallas").with_schedule(_StubSchedule(fc_ctx))


# -- CLI ---------------------------------------------------------------------

def test_cli_verifies_named_net(capsys):
    from repro.analysis.__main__ import main
    assert main(["--net", "alexnet", "--skip-determinism-lint"]) == 0
    out = capsys.readouterr().out
    assert "[alexnet@b1] OK" in out
    assert "0 findings" in out


def test_cli_verifies_zoo_variants_and_names_the_unplannable_one(capsys):
    """Every zoo variant verifies at its micro-batch with no finding:
    full-width VGG-16 plans its 224x224 stem in row bands, so no variant
    is left unplannable on the chip."""
    from repro.analysis.__main__ import main
    assert main(["--all-zoo-variants", "--skip-determinism-lint"]) == 0
    out = capsys.readouterr().out
    assert "[alexnet@b8] OK" in out and "[alexnet-int8@b" in out
    assert "[vgg16@b8] OK" in out
    assert "cannot be served" not in out


def test_cli_names_a_variant_no_band_fits_as_a_warning(capsys, monkeypatch):
    """Under a budget too small for even a one-row band of a stem, each
    variant is named with its layer as a warning rather than aborting
    the run."""
    import dataclasses

    from repro.analysis.__main__ import main
    from repro.core import engine
    from repro.core.accelerator import TPU_V5E
    small = dataclasses.replace(TPU_V5E, vmem_budget=256 * 1024)
    monkeypatch.setattr(engine, "current_chip", lambda: small)
    assert main(["--all-zoo-variants", "--skip-determinism-lint"]) == 0
    out = capsys.readouterr().out
    assert "[residency] vgg16/conv1: cannot be served on this chip" in out
    assert "[residency] alexnet/conv1: cannot be served on this chip" in out


def test_cli_requires_a_target():
    from repro.analysis.__main__ import main
    with pytest.raises(SystemExit):
        main([])
