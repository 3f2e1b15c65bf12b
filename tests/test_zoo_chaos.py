"""Fault-injected zoo serving: seeded chaos determinism, typed serving
errors, admission control (stale deadlines, bounded queues, predictive
shedding), retry-with-backoff and quarantine, int8 degraded fallback
(with bitwise parity against the *serving* variant), the isfinite
integrity guard, and the zero-unaccounted terminal-status invariant."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.dataflow import PlanError
from repro.core.perf_model import zoo_wave_cost
from repro.serve.errors import (CorruptOutputError, RequestShedError,
                                ServeError, StaleDeadlineError,
                                WaveTimeoutError)
from repro.serve.faults import ChaosConfig, FaultInjector
from repro.serve.zoo import (AdmissionConfig, EDFPolicy, FIFOPolicy,
                             ModelZooServer, RecoveryConfig, ZooRequest,
                             build_zoo)

RES = {"alexnet": 67}
WIDTH = 0.125

TERMINAL = ("served", "shed", "quarantined")


def fresh_zoo(names=("alexnet-int8",), *, max_batch=2, **kw):
    """A small fresh zoo per test (servers consume uids for life)."""
    return ModelZooServer(
        build_zoo(names, seed=0, in_res=RES, width_mult=WIDTH,
                  max_batch=max_batch), **kw)


def img(seed=0, res=67):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((res, res, 3)).astype(np.float32)


def submit_n(zoo, n, *, model="alexnet-int8", tenant="t", spacing=1e-3,
             deadline_rel=None, uid0=0, **kw):
    reqs = []
    for k in range(n):
        a = k * spacing
        reqs.append(ZooRequest(
            uid=uid0 + k, model=model, image=img(uid0 + k), tenant=tenant,
            arrival_s=a,
            deadline_s=None if deadline_rel is None else a + deadline_rel,
            **kw))
        zoo.submit(reqs[-1])
    return reqs


def assert_accounted(report, n):
    """The zero-unaccounted invariant: every admitted request ends in
    exactly one terminal status, with consistent terminal fields."""
    assert len(report.requests) == n
    assert report.unaccounted == ()
    for r in report.requests:
        assert r.status in TERMINAL
        if r.status == "served":
            assert r.error is None and r.finish_s is not None
        else:
            assert isinstance(r.error, ServeError)


# -- typed error hierarchy ---------------------------------------------------

def test_serving_error_hierarchy():
    assert issubclass(WaveTimeoutError, ServeError)
    assert issubclass(RequestShedError, ServeError)
    assert issubclass(StaleDeadlineError, RequestShedError)
    assert issubclass(CorruptOutputError, ServeError)
    assert issubclass(ServeError, RuntimeError)
    e = WaveTimeoutError("stalled past budget", uid=7, model="alexnet")
    assert e.uid == 7 and e.model == "alexnet"
    assert "stalled past budget" in str(e)
    # PlanError is re-exported so serving callers catch one module's types
    from repro.serve import errors
    assert errors.PlanError is PlanError


# -- seeded injector ---------------------------------------------------------

def test_injector_pure_function_of_seed_and_attempt():
    cfg = ChaosConfig(seed=3, dispatch_fail_rate=0.2, corrupt_rate=0.3,
                      stall_rate=0.3, stall_factors=(4.0, 24.0))
    a = [FaultInjector(cfg).wave_faults(i, 4) for i in range(40)]
    b = [FaultInjector(cfg).wave_faults(i, 4) for i in range(40)]
    assert a == b                         # fresh injector, same verdicts
    kinds = {f.kind for f in a}
    assert kinds <= {"none", "stall", "corrupt", "dispatch"}
    other = [FaultInjector(ChaosConfig(seed=4, dispatch_fail_rate=0.2,
                                       corrupt_rate=0.3, stall_rate=0.3,
                                       stall_factors=(4.0, 24.0)))
             .wave_faults(i, 4) for i in range(40)]
    assert a != other                     # the seed matters
    for f in a:
        if f.kind == "corrupt":
            assert 1 <= len(f.corrupt_rows) <= 4
            assert all(0 <= r < 4 for r in f.corrupt_rows)
        if f.kind == "stall":
            assert f.stall_factor in (4.0, 24.0)


def test_injector_zero_rates_always_clean():
    inj = FaultInjector(ChaosConfig(seed=0))
    assert all(inj.wave_faults(i, 4).is_clean for i in range(100))


def test_chaos_config_validation():
    with pytest.raises(ValueError, match="sum to"):
        ChaosConfig(dispatch_fail_rate=0.6, corrupt_rate=0.6)
    with pytest.raises(ValueError, match="stall_factors"):
        ChaosConfig(stall_factors=(1.0,))
    with pytest.raises(ValueError, match="corrupt_frac"):
        ChaosConfig(corrupt_frac=0.0)


def test_corrupt_array_and_dispatch_error_realizations():
    out = FaultInjector.corrupt_array(np.ones((5,), np.float32))
    assert not np.isfinite(out).all()
    assert np.isinf(out[0]) and np.isnan(out[1:]).all()
    err = FaultInjector.dispatch_error(3, "alexnet")
    assert isinstance(err, PlanError)
    assert "alexnet" in str(err) and "attempt3" in str(err)


# -- admission control -------------------------------------------------------

def test_stale_deadline_rejected_at_submit_as_typed_result():
    zoo = fresh_zoo()
    stale = ZooRequest(uid=0, model="alexnet-int8", image=img(),
                       arrival_s=1.0, deadline_s=0.5)
    assert zoo.submit(stale) is False
    assert stale.status == "shed"
    assert isinstance(stale.error, StaleDeadlineError)
    assert zoo.pending_count() == 0       # never scheduled...
    ok = ZooRequest(uid=1, model="alexnet-int8", image=img(1),
                    arrival_s=1.0, deadline_s=1.5)
    assert zoo.submit(ok) is True
    report = zoo.serve(execute=False)
    # ...but still accounted in the report, with a shed event
    assert_accounted(report, 2)
    assert report.shed == (stale,)
    assert any(e.kind == "shed" and e.uids == (0,) for e in report.events)
    assert ok.status == "served"
    # a stale uid stays consumed
    with pytest.raises(ValueError, match="duplicate request uid"):
        zoo.submit(ZooRequest(uid=0, model="alexnet-int8", image=img()))


def test_bounded_tenant_queue_sheds_overflow():
    zoo = fresh_zoo(admission=AdmissionConfig(max_queue=2))
    reqs = submit_n(zoo, 5, spacing=0.0)  # one burst instant
    other = ZooRequest(uid=99, model="alexnet-int8", image=img(99),
                       tenant="other", arrival_s=0.0)
    zoo.submit(other)                     # separate tenant: own bound
    report = zoo.serve(execute=False)
    assert_accounted(report, 6)
    assert [r.status for r in reqs] == \
        ["served", "served", "shed", "shed", "shed"]
    assert all(isinstance(r.error, RequestShedError)
               and not isinstance(r.error, StaleDeadlineError)
               for r in reqs[2:])
    assert other.status == "served"       # bounds are per tenant
    t = {s.tenant: s for s in report.per_tenant}
    assert t["t"].shed == 3 and t["t"].served == 2
    assert t["t"].shed_rate == pytest.approx(0.6)
    assert t["other"].shed == 0


def test_predictive_shedding_rejects_infeasible_deadline():
    zoo = fresh_zoo(admission=AdmissionConfig(predictive_shedding=True))
    cost1 = zoo_wave_cost("alexnet", 1, bytes_w=1).total_s
    # deadline below even the solo-wave best case: certain miss -> shed
    r = submit_n(zoo, 1, deadline_rel=cost1 * 0.5)[0]
    report = zoo.serve(execute=False)
    assert_accounted(report, 1)
    assert r.status == "shed" and isinstance(r.error, RequestShedError)
    assert any(e.kind == "shed" for e in report.events)
    # without predictive shedding the same request is served (late)
    zoo2 = fresh_zoo()
    r2 = submit_n(zoo2, 1, deadline_rel=cost1 * 0.5)[0]
    zoo2.serve(execute=False)
    assert r2.status == "served" and r2.missed_deadline


def test_predictive_degrade_reroutes_to_int8_and_parity_holds():
    zoo = fresh_zoo(("alexnet", "alexnet-int8"),
                    admission=AdmissionConfig(predictive_shedding=True))
    fp32 = zoo_wave_cost("alexnet", 1, bytes_w=4).total_s
    int8 = zoo_wave_cost("alexnet", 1, bytes_w=1).total_s
    assert int8 < fp32
    # deadline between the two best cases: fp32 certainly misses, the
    # int8 sibling makes it -> the eligible request reroutes
    r = ZooRequest(uid=0, model="alexnet", image=img(), arrival_s=0.0,
                   deadline_s=(fp32 + int8) / 2)
    zoo.submit(r)
    opt_out = ZooRequest(uid=1, model="alexnet", image=img(1),
                         arrival_s=10.0, deadline_s=10.0 + (fp32 + int8) / 2,
                         allow_degraded=False)
    zoo.submit(opt_out)                   # declines degraded service
    report = zoo.serve()                  # executed: parity matters here
    assert_accounted(report, 2)
    assert r.status == "served" and r.served_by == "alexnet-int8"
    assert r.degraded and not r.missed_deadline
    assert any(e.kind == "degrade" and e.uids == (0,)
               for e in report.events)
    assert report.degraded_served == 1
    # opted-out request cannot be degraded: certain miss -> shed
    assert opt_out.status == "shed"
    # bitwise parity against the variant that SERVED it (the int8 one)
    from repro.models import cnn
    import jax.numpy as jnp
    m = zoo.models["alexnet-int8"]
    ref = np.asarray(cnn.cnn_forward(m.spec.net, m.params,
                                     jnp.asarray(img())[None],
                                     eng=m.server.engine))[0]
    assert np.array_equal(r.logits, ref)
    assert np.isfinite(r.logits).all()


# -- retry / quarantine / health ---------------------------------------------

def test_dispatch_failures_retry_then_quarantine():
    zoo = fresh_zoo(
        faults=FaultInjector(ChaosConfig(seed=0, dispatch_fail_rate=1.0)),
        recovery=RecoveryConfig(max_retries=2, fail_after=2))
    reqs = submit_n(zoo, 2, spacing=0.0)
    report = zoo.serve()                  # executed: PlanError is raised
    assert_accounted(report, 2)
    for r in reqs:
        assert r.status == "quarantined"
        assert r.retries == 3             # initial + 2 retries
        assert isinstance(r.error, ServeError)
        assert r.logits is None and not r.done
    assert all(d.fault == "dispatch" and d.conv_s == 0.0
               for d in report.decisions)
    assert dict(report.health)["alexnet-int8"] == "failed"
    kinds = [e.kind for e in report.events]
    assert "dispatch" in kinds and "retry" in kinds \
        and "quarantine" in kinds and "health" in kinds
    assert report.retry_count == 6


def test_hard_stall_times_out_and_quarantines_as_timeout():
    zoo = fresh_zoo(
        faults=FaultInjector(ChaosConfig(seed=0, stall_rate=1.0,
                                         stall_factors=(24.0,))),
        recovery=RecoveryConfig(max_retries=1, wave_timeout_factor=8.0))
    r = submit_n(zoo, 1)[0]
    report = zoo.serve(execute=False)
    assert_accounted(report, 1)
    assert r.status == "quarantined"
    assert isinstance(r.error, WaveTimeoutError)
    # the aborted wave occupied the arrays for timeout_factor x modeled
    cost = zoo_wave_cost("alexnet", 1, bytes_w=1)
    assert report.decisions[0].fault == "timeout"
    assert report.decisions[0].conv_s == pytest.approx(cost.conv_s * 8.0)
    assert report.decisions[0].stall_factor == 24.0


def test_mild_stall_serves_late_and_flags_straggler():
    cfg = ChaosConfig(seed=0, stall_rate=0.25, stall_factors=(4.0,))
    # the injector is pure: pick a seed whose draw sequence is
    # clean,clean,clean,stall so the straggler fires past monitor warmup
    seed = next(
        s for s in range(500)
        if all(FaultInjector(ChaosConfig(seed=s, stall_rate=0.25,
                                         stall_factors=(4.0,)))
               .wave_faults(a, 1).kind == "none" for a in range(3))
        and FaultInjector(ChaosConfig(seed=s, stall_rate=0.25,
                                      stall_factors=(4.0,)))
        .wave_faults(3, 1).kind == "stall")
    zoo = fresh_zoo(
        max_batch=1,
        faults=FaultInjector(ChaosConfig(seed=seed, stall_rate=0.25,
                                         stall_factors=(4.0,))),
        recovery=RecoveryConfig(straggler_warmup=3, wave_timeout_factor=8.0))
    submit_n(zoo, 4, spacing=1e-1)        # four solo waves
    report = zoo.serve(execute=False)
    assert_accounted(report, 4)
    assert all(r.status == "served" for r in report.requests)
    d = report.decisions[3]
    assert d.fault == "stall" and d.stall_factor == 4.0
    cost = zoo_wave_cost("alexnet", 1, bytes_w=1)
    assert d.conv_s == pytest.approx(cost.conv_s * 4.0)
    assert any(e.kind == "stall" for e in report.events)     # verdict
    assert dict(report.health)["alexnet-int8"] == "degraded"
    assert cfg.stall_rate == 0.25         # config untouched by the scan


def test_corrupt_wave_quarantines_rows_via_integrity_guard():
    zoo = fresh_zoo(
        faults=FaultInjector(ChaosConfig(seed=0, corrupt_rate=1.0,
                                         corrupt_frac=0.5)),
        recovery=RecoveryConfig(max_retries=0))
    reqs = submit_n(zoo, 2, spacing=0.0)  # one wave of two rows
    report = zoo.serve()                  # executed: NaN really injected
    assert_accounted(report, 2)
    statuses = sorted(r.status for r in reqs)
    assert statuses == ["quarantined", "served"]
    for r in reqs:
        if r.status == "quarantined":
            assert isinstance(r.error, CorruptOutputError)
            assert r.logits is None       # garbage never delivered
        else:
            assert np.isfinite(r.logits).all()
    assert report.decisions[0].fault == "corrupt"


def test_retry_after_transient_fault_eventually_serves():
    # dispatch fails on attempt 0 only: the retry must serve with real
    # logits, and the extra attempt is visible in the accounting
    class OneShot(FaultInjector):
        def wave_faults(self, attempt, batch):
            from repro.serve.faults import WaveFaults
            if attempt == 0:
                return WaveFaults(attempt=attempt, kind="dispatch")
            return WaveFaults(attempt=attempt, kind="none")

    zoo = fresh_zoo(faults=OneShot(ChaosConfig(seed=0)),
                    recovery=RecoveryConfig(max_retries=2))
    r = submit_n(zoo, 1)[0]
    report = zoo.serve()
    assert_accounted(report, 1)
    assert r.status == "served" and r.retries == 1
    assert r.logits is not None and np.isfinite(r.logits).all()
    assert [d.fault for d in report.decisions] == ["dispatch", "none"]
    # backoff: the retry dispatched strictly after the failed attempt
    assert report.decisions[1].t_s > report.decisions[0].t_s


def test_executor_exception_quarantines_instead_of_wedging():
    zoo = fresh_zoo()
    r = submit_n(zoo, 1)[0]
    srv = zoo.models["alexnet-int8"].server

    def boom():
        raise RuntimeError("array bringup failed")
    srv.step_wave = boom
    report = zoo.serve()                  # must not raise
    assert_accounted(report, 1)
    assert r.status == "quarantined"
    assert isinstance(r.error, ServeError)
    assert "RuntimeError" in str(r.error)


# -- healthy-path equivalence ------------------------------------------------

def test_zero_rate_injector_is_bit_identical_to_no_injector():
    zoo_a = fresh_zoo(("alexnet", "alexnet-int8"), policy=EDFPolicy())
    zoo_b = fresh_zoo(("alexnet", "alexnet-int8"), policy=EDFPolicy(),
                      faults=FaultInjector(ChaosConfig(seed=123)))
    for z in (zoo_a, zoo_b):
        for k, model in enumerate(("alexnet", "alexnet-int8") * 3):
            z.submit(ZooRequest(uid=k, model=model, image=img(k),
                                arrival_s=k * 2e-4,
                                deadline_s=k * 2e-4 + 5e-3))
    ra = zoo_a.serve(execute=False)
    rb = zoo_b.serve(execute=False)
    assert ra.decisions == rb.decisions   # frozen dataclass equality
    assert ra.events == rb.events == ()
    assert [r.status for r in ra.requests] == \
        [r.status for r in rb.requests] == ["served"] * 6
    assert [r.finish_s for r in ra.requests] == \
        [r.finish_s for r in rb.requests]
    assert ra.retry_count == rb.retry_count == 0
    assert all(s == "healthy" for _, s in rb.health)


def test_default_configs_preserve_legacy_serve_contract():
    # FIFO, no faults, no admission config: every request served in the
    # legacy shape (done flag, logits, report fields populated)
    zoo = fresh_zoo(policy=FIFOPolicy())
    reqs = submit_n(zoo, 3, spacing=1e-4)
    report = zoo.serve()
    assert_accounted(report, 3)
    assert all(r.done and r.logits is not None for r in reqs)
    assert report.shed == () and report.quarantined == ()
    assert report.events == () and report.makespan_s > 0.0
    assert report.shed_rate == 0.0 and report.degraded_served == 0


# -- uploads made ahead under faults -----------------------------------------

def _uploads():
    from repro.serve import telemetry
    c = telemetry.counts()
    return np.array([c.get((k, "cnn.upload"), 0)
                     for k in ("cnn.uploads", "cnn.upload_ahead")])


def _assert_bitwise_unbatched(zoo, reqs):
    import jax.numpy as jnp

    from repro.models import cnn
    for r in reqs:
        m = zoo.models[r.model]
        ref = cnn.cnn_forward(m.spec.net, m.params,
                              jnp.asarray(r.image)[None],
                              eng=m.server.engine)
        np.testing.assert_array_equal(np.asarray(ref)[0], r.logits)


def test_wave_that_raises_drops_the_upload_made_for_the_next(monkeypatch):
    """Wave 0 raises at its barrier, after wave 1's upload was issued
    inside it: wave 0's rows are quarantined, the lane drops that upload,
    wave 1 uploads its own and serves bitwise, and the lane is left
    empty for the next call."""
    from repro.models import cnn

    zoo = fresh_zoo(policy=FIFOPolicy())
    srv = zoo.models["alexnet-int8"].server
    reqs = submit_n(zoo, 6, spacing=0.0)
    fc, calls = cnn.cnn_fc_stage, []

    class Unreadable:
        def __array__(self, *a, **kw):
            raise RuntimeError("barrier failed")

    def first_fails(*a, **kw):
        calls.append(1)
        out = fc(*a, **kw)
        return Unreadable() if len(calls) == 1 else out

    monkeypatch.setattr(cnn, "cnn_fc_stage", first_fails)
    up0 = _uploads()
    report = zoo.serve()
    monkeypatch.undo()
    assert_accounted(report, 6)
    assert [r.status for r in reqs] == ["quarantined"] * 2 + ["served"] * 4
    assert "RuntimeError" in str(reqs[0].error)
    # waves 0, 1 (ahead, dropped), 1 again, 2 (ahead, used)
    assert (_uploads() - up0).tolist() == [4, 2]
    assert srv.queue == [] and srv._staged is None
    _assert_bitwise_unbatched(zoo, reqs[2:])
    more = submit_n(zoo, 2, spacing=0.0, uid0=10)
    assert_accounted(zoo.serve(), 2)
    _assert_bitwise_unbatched(zoo, more)


def test_retried_waves_serve_bitwise_with_uploads_ahead():
    """Modeled dispatch failures put retries and never-run attempts
    between the waves that run: every served row is still bitwise its
    unbatched forward, and no upload is used by a wave it was not made
    for."""
    zoo = fresh_zoo(policy=FIFOPolicy(),
                    faults=FaultInjector(ChaosConfig(
                        seed=3, dispatch_fail_rate=0.4)),
                    recovery=RecoveryConfig(max_retries=3))
    reqs = submit_n(zoo, 10, spacing=0.0)
    up0 = _uploads()
    report = zoo.serve()
    assert_accounted(report, 10)
    assert report.retry_count > 0
    served = [r for r in reqs if r.status == "served"]
    assert served
    _assert_bitwise_unbatched(zoo, served)
    ran = sum(1 for d in report.decisions if d.fault in ("none", "corrupt"))
    assert (_uploads() - up0)[0] == ran
