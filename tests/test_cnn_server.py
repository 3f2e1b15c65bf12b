"""Batched CNN serving: the micro-batch coalescing front-end that exploits
the batch-amortized SA-FC dataflow (the CNN analogue of ServeEngine)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dataflow import FCPlan
from repro.core.engine import DispatchPolicy, Engine
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


def test_server_coalesces_singles_into_one_dispatch(alexnet_params):
    """Acceptance: >= 3 single-image submissions ride ONE planner-preferred
    micro-batch dispatch, every FC layer in the wave's DispatchTrace
    carrying an FCPlan, resolved from the compiled batch-variant
    schedule."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=8)
    reqs = _requests(4)
    for r in reqs:
        srv.submit(r)
    done = srv.run()
    assert len(done) == 4 and all(r.done for r in done)
    assert len(srv.waves) == 1                     # one coalesced dispatch
    wave = srv.waves[0]
    assert wave.batch == 4 and wave.uids == (0, 1, 2, 3)
    fc_recs = wave.fc_records
    assert len(fc_recs) == 3                       # fc1..fc3 of AlexNet
    assert all(isinstance(r.fc_plan, FCPlan) for r in fc_recs)
    assert all(r.schedule == "hit" for r in fc_recs)
    # the whole wave resolved from the compiled batch-variant schedule
    assert wave.schedule_hits == len([r for r in wave.trace
                                      if r.schedule == "hit"])
    assert wave.schedule_hits >= 8                 # 5 convs + 3 fcs


def test_server_outputs_bitwise_equal_unbatched(alexnet_params):
    """Acceptance: batching changes traffic, never math — each request's
    logits are bitwise equal to its own unbatched forward (rows are
    independent in every kernel and the b<=16 batch variants pad to the
    same tiles)."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=8)
    reqs = _requests(3, seed=1)
    for r in reqs:
        srv.submit(r)
    srv.run()
    eng = Engine(backend="pallas")
    for r in reqs:
        single = cnn.cnn_forward("alexnet", alexnet_params,
                                 jnp.asarray(r.image)[None], eng=eng)
        np.testing.assert_array_equal(np.asarray(single)[0], r.logits)


def test_server_microbatch_is_planner_preferred(alexnet_params):
    """The admission size IS the planner's resident batch tile: a VMEM
    budget that cannot hold 512 samples shrinks the micro-batch to what
    one weight pass can amortize.  (The budget must still fit the conv
    stack, which the server plans when it is built.)"""
    roomy = CNNServer("alexnet", alexnet_params, in_res=RES,
                      width_mult=WIDTH, max_batch=512)
    assert roomy.microbatch == 512
    tight_eng = Engine(backend="pallas",
                       policy=DispatchPolicy(vmem_budget=1536 * 1024))
    tight = CNNServer("alexnet", alexnet_params, in_res=RES,
                      width_mult=WIDTH, max_batch=512, engine=tight_eng)
    assert tight.microbatch < 512
    # and it matches the plan of the dominant FC layer exactly
    k, n = max(((p["w"].shape) for s, p in
                zip(cnn.NETWORKS["alexnet"][0], alexnet_params)
                if s.kind == "fc"), key=lambda s: s[0] * s[1])
    plan = tight_eng.policy.plan_fc(512, n, k, act_bytes=4, weight_bytes=4,
                                    regime="sa_fc")
    assert tight.microbatch == plan.bb


def test_server_drains_queue_in_waves(alexnet_params):
    """More requests than one micro-batch: the queue drains in
    planner-sized waves, preserving order and per-request identity."""
    eng = Engine(backend="pallas",
                 policy=DispatchPolicy(vmem_budget=2 * 1024 * 1024))
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=4, engine=eng)
    srv.microbatch = 2                      # force small waves for the test
    reqs = _requests(5, seed=2)
    for r in reqs:
        srv.submit(r)
    done = srv.run()
    assert len(done) == 5
    assert [w.batch for w in srv.waves] == [2, 2, 1]
    assert [u for w in srv.waves for u in w.uids] == [0, 1, 2, 3, 4]
    assert all(r.logits is not None and r.logits.shape == (1000,)
               for r in done)


def test_server_rejects_wrong_shape(alexnet_params):
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH)
    with pytest.raises(ValueError, match="image shape"):
        srv.submit(CNNRequest(uid=0, image=np.zeros((5, 5, 3), np.float32)))


def test_server_run_on_empty_queue_is_a_noop(alexnet_params):
    """Edge case: draining a server nobody submitted to returns [] (both
    entries, both modes) and files no waves."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH)
    assert srv.run() == []
    assert srv.run(pipelined=False) == []
    assert srv.step_wave() == []
    assert srv.drain() == []
    assert srv.waves == []


def test_server_final_wave_smaller_than_planner_microbatch(alexnet_params):
    """Edge case: a queue that is not a multiple of FCPlan.bb ends with a
    partial wave — it still dispatches (smaller batch variant) and its
    logits match the unbatched forward bitwise."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=8)
    assert srv.preferred_microbatch == 8
    reqs = _requests(3, seed=3)             # 3 < bb: one partial wave
    for r in reqs:
        srv.submit(r)
    done = srv.run()
    assert len(done) == 3
    assert [w.batch for w in srv.waves] == [3]
    eng = Engine(backend="pallas")
    single = cnn.cnn_forward("alexnet", alexnet_params,
                             jnp.asarray(reqs[0].image)[None], eng=eng)
    np.testing.assert_array_equal(np.asarray(single)[0], reqs[0].logits)


def test_server_rejects_duplicate_uids(alexnet_params):
    """Edge case: uids name one request for the server's lifetime —
    resubmitting one raises, even after the original already completed."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH)
    srv.submit(_requests(1)[0])
    with pytest.raises(ValueError, match="duplicate request uid 0"):
        srv.submit(_requests(1)[0])
    srv.run()
    with pytest.raises(ValueError, match="duplicate request uid 0"):
        srv.submit(_requests(1)[0])


def test_server_step_wave_and_drain(alexnet_params):
    """The zoo-facing wave-executor API: step_wave() serves exactly one
    micro-batch per call; drain() flushes the tail (including a final
    partial wave)."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=4)
    srv.microbatch = 2
    reqs = _requests(5, seed=4)
    for r in reqs:
        srv.submit(r)
    first = srv.step_wave()
    assert [r.uid for r in first] == [0, 1]
    assert len(srv.queue) == 3
    rest = srv.drain()
    assert [r.uid for r in rest] == [2, 3, 4]
    assert [w.batch for w in srv.waves] == [2, 2, 1]
    assert all(r.done for r in reqs)


def test_server_preferred_microbatch_is_planner_pinned(alexnet_params):
    """preferred_microbatch is the immutable planner answer; microbatch
    is the mutable admission cap initialized from it."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=8)
    assert srv.microbatch == srv.preferred_microbatch == 8
    srv.microbatch = 2
    assert srv.preferred_microbatch == 8    # the planner's answer persists


def test_server_refuses_an_unplannable_conv_stack_when_built(alexnet_params):
    """A conv stack no tiling fits raises PlanError naming the layer when
    the server is built — never later, inside a wave a zoo would
    quarantine."""
    from repro.core.dataflow import PlanError
    eng = Engine(backend="pallas",
                 policy=DispatchPolicy(vmem_budget=512 * 1024))
    with pytest.raises(PlanError) as ei:
        CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                  max_batch=8, engine=eng)
    assert ei.value.op == "conv1"


# -- the wave's upload ---------------------------------------------------------

def _uploads():
    """Process totals of ``cnn.uploads`` and ``cnn.upload_ahead``."""
    from repro.serve import telemetry
    c = telemetry.counts()
    return tuple(c.get((k, "cnn.upload"), 0)
                 for k in ("cnn.uploads", "cnn.upload_ahead"))


def _assert_bitwise_unbatched(srv, reqs):
    for r in reqs:
        ref = cnn.cnn_forward("alexnet", srv.params,
                              jnp.asarray(r.image)[None], eng=srv.engine)
        np.testing.assert_array_equal(np.asarray(ref)[0], r.logits)


def test_a_wave_of_host_images_is_one_device_put(alexnet_params,
                                                 monkeypatch):
    """A wave's host images reach the device in one ``jax.device_put`` of
    the stacked wave, and each row serves bitwise as its unbatched
    forward."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=8)
    reqs = _requests(8, seed=5)
    for r in reqs:
        srv.submit(r)
    put, shapes = jax.device_put, []

    def counted(x, *a, **kw):
        shapes.append(np.shape(x))
        return put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", counted)
    assert len(srv.step_wave()) == 8
    monkeypatch.undo()
    assert shapes == [(8, RES, RES, 3)]
    _assert_bitwise_unbatched(srv, reqs)


def test_a_wave_of_device_images_serves_bitwise(alexnet_params):
    """Images already on the device are stacked there; the wave serves
    bitwise as the host-image path does."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=8)
    reqs = [CNNRequest(uid=r.uid, image=jax.device_put(r.image))
            for r in _requests(3, seed=6)]
    for r in reqs:
        srv.submit(r)
    up0 = _uploads()
    assert len(srv.step_wave()) == 3
    assert np.subtract(_uploads(), up0).tolist() == [1, 0]
    _assert_bitwise_unbatched(srv, reqs)


def test_prefetched_rows_are_uploaded_ahead_and_used_only_when_exact(
        alexnet_params):
    """prefetch(): the next wave's upload is issued inside the current
    step_wave; a wave of exactly those rows uses it, a wave of any other
    rows uploads its own and the early upload is dropped."""
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=8)
    srv.microbatch = 2
    reqs = _requests(6, seed=7)
    up0 = _uploads()

    def delta():
        return np.subtract(_uploads(), up0).tolist()

    srv.submit(reqs[0])
    srv.submit(reqs[1])
    srv.prefetch(reqs[2:4])
    assert [r.uid for r in srv.step_wave()] == [0, 1]
    assert delta() == [2, 1] and srv.queue == []  # prefetch never queues
    srv.submit(reqs[2])
    srv.submit(reqs[3])
    srv.prefetch(reqs[4:6])
    assert [r.uid for r in srv.step_wave()] == [2, 3]
    assert delta() == [3, 2]                      # wave 2,3 used its upload
    srv.submit(reqs[4])                           # only part of the rows
    assert [r.uid for r in srv.step_wave()] == [4]
    assert delta() == [4, 2] and srv._staged is None
    srv.submit(reqs[5])
    srv.prefetch(reqs[:1])
    assert [r.uid for r in srv.step_wave()] == [5]
    assert srv.step_wave() == []                  # an empty queue forgets
    assert delta() == [6, 3]
    _assert_bitwise_unbatched(srv, reqs)


def test_prefetch_skips_rows_submit_would_refuse(alexnet_params):
    srv = CNNServer("alexnet", alexnet_params, in_res=RES, width_mult=WIDTH,
                    max_batch=8)
    srv.submit(_requests(1, seed=8)[0])
    bad = CNNRequest(uid=9, image=np.zeros((RES, RES + 1, 3), np.float32))
    srv.prefetch([bad])
    up0 = _uploads()
    assert len(srv.step_wave()) == 1
    assert np.subtract(_uploads(), up0).tolist() == [1, 0]
    with pytest.raises(ValueError, match="image shape"):
        srv.submit(bad)
