"""Dual-array pipelined serving: stage-split schedules, overlapped waves
with bitwise parity, stage/wave-tagged traces, and the analytic
pipeline-makespan / bottleneck-crossover models."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import perf_model as PM
from repro.core.dataflow import FCPlan
from repro.core.engine import Engine
from repro.core.roofline import pipeline_overlap_from_schedule
from repro.core.schedule import LayerSchedule
from repro.models import cnn
from repro.serve.cnn_server import CNNRequest, CNNServer

RES, WIDTH = 67, 0.125


@pytest.fixture(scope="module")
def alexnet_params():
    return cnn.init_cnn("alexnet", jax.random.PRNGKey(0), in_res=RES,
                        width_mult=WIDTH)


def _requests(n, seed=0):
    rng = np.random.default_rng(seed)
    return [CNNRequest(uid=i,
                       image=rng.standard_normal((RES, RES, 3))
                       .astype(np.float32))
            for i in range(n)]


# ---------------------------------------------------------------------------
# the stage split itself
# ---------------------------------------------------------------------------
def test_stage_composition_bitwise_equals_forward(alexnet_params):
    """cnn_forward IS conv_stage o fc_stage — same dispatches, same
    kernels, bitwise-equal logits."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, RES, RES, 3),
                          jnp.float32)
    eng = Engine(backend="pallas")
    full = cnn.cnn_forward("alexnet", alexnet_params, x, eng=eng)
    feats = cnn.cnn_conv_stage("alexnet", alexnet_params, x, eng=eng)
    split = cnn.cnn_fc_stage("alexnet", alexnet_params, feats, eng=eng)
    assert feats.ndim == 2 and feats.shape[0] == 2
    np.testing.assert_array_equal(np.asarray(full), np.asarray(split))


def test_stage_schedules_partition_full_schedule(alexnet_params):
    """The conv/fc stage schedules carve the full compiled schedule into
    two disjoint halves: every conv entry in the conv stage, every FC
    entry in the fc stage, nothing shared, nothing lost."""
    kw = dict(batch=4, in_res=RES, width_mult=WIDTH)
    full = LayerSchedule.compile_cnn("alexnet", **kw)
    conv_s, fc_s = LayerSchedule.compile_cnn_stages("alexnet", **kw)
    assert dict(conv_s.conv_entries) == dict(full.conv_entries)
    assert len(conv_s) == 0                       # no matmul entries
    assert dict(fc_s) == {k: full[k] for k in full}
    assert len(fc_s.conv_entries) == 0
    # memoized like every schedule
    again = LayerSchedule.compile_cnn("alexnet", stage="conv", **kw)
    assert again is conv_s
    with pytest.raises(ValueError, match="stage"):
        LayerSchedule.compile_cnn("alexnet", stage="bogus", **kw)


# ---------------------------------------------------------------------------
# the pipelined server
# ---------------------------------------------------------------------------
def test_pipelined_bitwise_equal_sequential_and_unbatched(alexnet_params):
    """Acceptance: pipelined logits are bitwise equal to the sequential
    path (and to the unbatched forward) — overlap changes when a stage
    is waited on, never what it computes."""
    reqs_p = _requests(5, seed=3)
    reqs_s = _requests(5, seed=3)
    srv_p = CNNServer("alexnet", alexnet_params, in_res=RES,
                      width_mult=WIDTH, max_batch=2, pipeline=True)
    srv_s = CNNServer("alexnet", alexnet_params, in_res=RES,
                      width_mult=WIDTH, max_batch=2, pipeline=False)
    for rp, rs in zip(reqs_p, reqs_s):
        srv_p.submit(rp)
        srv_s.submit(rs)
    done_p = srv_p.run()
    done_s = srv_s.run()
    assert len(done_p) == len(done_s) == 5
    assert [w.batch for w in srv_p.waves] == [2, 2, 1]
    assert [w.wave for w in srv_p.waves] == [0, 1, 2]
    for rp, rs in zip(sorted(done_p, key=lambda r: r.uid),
                      sorted(done_s, key=lambda r: r.uid)):
        assert rp.uid == rs.uid
        np.testing.assert_array_equal(rp.logits, rs.logits)
    eng = Engine(backend="pallas")
    one = cnn.cnn_forward("alexnet", alexnet_params,
                          jnp.asarray(reqs_p[0].image)[None], eng=eng)
    np.testing.assert_array_equal(np.asarray(one)[0], done_p[0].logits)


def test_wave_reports_stage_and_wave_tagged(alexnet_params):
    """Every record in a pipelined wave carries its stage/wave provenance:
    the conv trace is all stage='conv', the fc trace all stage='fc' (with
    FCPlans resolved from the fc-stage schedule), and the combined trace
    is their concatenation."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=2)
    for r in _requests(4, seed=4):
        srv.submit(r)
    srv.run()
    assert len(srv.waves) == 2
    for i, w in enumerate(srv.waves):
        assert w.wave == i
        assert len(w.conv_trace) > 0 and len(w.fc_trace) > 0
        assert all(r.stage == "conv" and r.wave == i for r in w.conv_trace)
        assert all(r.stage == "fc" and r.wave == i for r in w.fc_trace)
        assert len(w.trace) == len(w.conv_trace) + len(w.fc_trace)
        assert len(w.trace.by_stage("conv")) == len(w.conv_trace)
        assert len(w.trace.by_wave(i)) == len(w.trace)
        fc_recs = w.fc_records
        assert len(fc_recs) == 3                  # fc1..fc3
        assert all(isinstance(r.fc_plan, FCPlan) for r in fc_recs)
        assert all(r.schedule == "hit" for r in fc_recs)
        # conv stage resolved from the conv-stage schedule too
        assert all(r.schedule == "hit" for r in w.conv_trace
                   if r.conv_plan is not None)


# ---------------------------------------------------------------------------
# VGG-16 end-to-end through Engine/compile_cnn (the second paper network
# finally executes in the fast tier, not just the analytic model)
# ---------------------------------------------------------------------------
def test_vgg16_end_to_end_through_engine_schedule():
    params = cnn.init_cnn("vgg16", jax.random.PRNGKey(0), in_res=32,
                          width_mult=WIDTH)
    sched = LayerSchedule.compile_cnn("vgg16", batch=1, in_res=32,
                                      width_mult=WIDTH)
    eng = Engine(backend="pallas").with_schedule(sched)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3),
                          jnp.float32)
    with eng.tracing() as tr:
        logits = cnn.cnn_forward("vgg16", params, x, eng=eng)
    assert logits.shape == (1, 1000)
    assert bool(jnp.all(jnp.isfinite(logits)))
    convs = [r for r in tr if r.conv_plan is not None]
    fcs = [r for r in tr if r.fc_plan is not None]
    assert len(convs) == 13                       # VGG-16's conv stack
    assert len(fcs) == 3
    # every one of the 5 pool stages is accounted for: fused into a conv's
    # flush epilogue or dispatched as a standalone pool record
    fused_pools = sum(r.conv_plan.fuse_pool for r in convs)
    standalone = len(tr.by_regime("pool"))
    assert fused_pools + standalone == 5
    # the whole net resolved from the compiled schedule
    assert all(r.schedule == "hit" for r in convs + fcs)


def test_vgg16_pipelined_server_parity():
    """VGG-16 through the pipelined server: both paper networks serve."""
    params = cnn.init_cnn("vgg16", jax.random.PRNGKey(0), in_res=32,
                          width_mult=WIDTH)
    rng = np.random.default_rng(5)
    reqs = [CNNRequest(uid=i, image=rng.standard_normal((32, 32, 3))
                       .astype(np.float32)) for i in range(2)]
    srv = CNNServer("vgg16", params, in_res=32, width_mult=WIDTH,
                    max_batch=1, pipeline=True)
    for r in reqs:
        srv.submit(r)
    done = srv.run()
    assert len(done) == 2 and len(srv.waves) == 2     # overlapped waves
    eng = Engine(backend="pallas")
    one = cnn.cnn_forward("vgg16", params,
                          jnp.asarray(reqs[0].image)[None], eng=eng)
    np.testing.assert_array_equal(np.asarray(one)[0], done[0].logits)


# ---------------------------------------------------------------------------
# the analytic makespan / crossover models
# ---------------------------------------------------------------------------
def test_pipeline_makespan_overlaps():
    for net in ("alexnet", "vgg16"):
        m1 = PM.pipeline_makespan(net, batch=4, waves=1)
        assert m1.makespan_ratio == pytest.approx(1.0)   # nothing to hide
        prev = 1.0
        for waves in (2, 4, 16, 64):
            m = PM.pipeline_makespan(net, batch=4, waves=waves)
            assert 1.0 < m.makespan_ratio < 2.0
            assert m.makespan_ratio > prev        # more waves, more hidden
            prev = m.makespan_ratio
            assert m.pipelined_cycles < m.serial_cycles
            assert m.bottleneck in ("sa_conv", "sa_fc")
            assert 0.0 < m.overlap_efficiency <= 1.0
        # asymptote: ratio -> 1 + min/max as waves -> inf
        big = PM.pipeline_makespan(net, batch=4, waves=10_000)
        assert big.makespan_ratio == pytest.approx(
            1.0 + big.overlap_efficiency, rel=1e-2)


def test_stage_cycles_match_per_sample_model_at_b1():
    """The batch-aware stage cycles reduce to the existing per-sample
    cycle model at batch 1 (same Fig. 1 accounting)."""
    from repro.core.accelerator import MPNA_PAPER
    for net in ("alexnet", "vgg16"):
        t = PM.network_cycles(net, MPNA_PAPER.sa_conv, fc_on="sa_fc")
        assert PM.conv_stage_cycles(net, 1) == pytest.approx(t.conv_cycles)
        assert PM.fc_stage_cycles(net, 1) == pytest.approx(t.fc_cycles)


def test_tpu_crossover_batch_pins():
    """The FC->CONV bottleneck flip is a planner-pinned quantity (like
    FCPlan.flip_batch): AlexNet's 224 MiB fp32 head keeps it FC-bound to
    b=29 while conv-dominated VGG-16 flips at b=5; int8 weights (1
    byte/weight) pull both in."""
    assert PM.tpu_pipeline_crossover_batch("alexnet") == 29
    assert PM.tpu_pipeline_crossover_batch("vgg16") == 5
    assert PM.tpu_pipeline_crossover_batch("alexnet", bytes_w=1) == 8
    assert PM.tpu_pipeline_crossover_batch("vgg16", bytes_w=1) == 2
    # below the crossover the wave is FC-bound, above it CONV-bound
    c, f = PM.pipeline_stage_seconds("alexnet", 28)
    assert f > c
    c, f = PM.pipeline_stage_seconds("alexnet", 29)
    assert c >= f


def test_pipeline_overlap_from_schedule_report(alexnet_params):
    """The schedule-side overlap report agrees with the makespan formula
    on the exact plans the pipelined server runs."""
    cs, fs = LayerSchedule.compile_cnn_stages("alexnet", batch=4,
                                              in_res=RES, width_mult=WIDTH)
    rep = pipeline_overlap_from_schedule(cs, fs, waves=8)
    assert rep["waves"] == 8
    assert rep["conv_stage"]["seconds"] > 0
    assert rep["fc_stage"]["seconds"] > 0
    assert rep["bottleneck"] in ("sa_conv", "sa_fc")
    assert 0.0 < rep["overlap_efficiency"] <= 1.0
    assert 1.0 < rep["makespan_ratio"] < 2.0
    c, f = rep["conv_stage"]["seconds"], rep["fc_stage"]["seconds"]
    assert rep["serial_s"] == pytest.approx(8 * (c + f))
    assert rep["pipelined_s"] == pytest.approx(c + f + 7 * max(c, f))
    # stage HBM/flops come from the stage plans, so they partition the
    # full schedule's totals
    from repro.core.roofline import terms_from_schedule
    full = terms_from_schedule(
        LayerSchedule.compile_cnn("alexnet", batch=4, in_res=RES,
                                  width_mult=WIDTH))
    assert rep["conv_stage"]["flops"] + rep["fc_stage"]["flops"] == \
        pytest.approx(full.flops_per_chip)
    assert rep["conv_stage"]["hbm_bytes"] + rep["fc_stage"]["hbm_bytes"] \
        == pytest.approx(full.hbm_bytes_per_chip)
