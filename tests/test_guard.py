"""The zoo's integrity guard: ``errors.all_finite`` agrees with the
device's ``jnp.isfinite`` on every float class, and
``ModelZooServer._guard`` decides on the host copy of the logits, with no
device call, quarantining injected and genuine non-finite rows alike."""
from __future__ import annotations

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.serve import telemetry
from repro.serve.errors import CorruptOutputError, all_finite
from repro.serve.faults import ChaosConfig, FaultInjector
from repro.serve.zoo import (ModelZooServer, RecoveryConfig, ZooRequest,
                             build_zoo)

RES, WIDTH = 67, 0.125
DTYPES = (np.float32, jnp.bfloat16)
SPECIALS = ("nan", "+inf", "-inf", "max", "subnormal", "-0.0", "finite")


def _special(name: str, dtype) -> float | None:
    info = ml_dtypes.finfo(dtype)
    return {"nan": np.nan, "+inf": np.inf, "-inf": -np.inf,
            "max": info.max, "subnormal": info.smallest_subnormal,
            "-0.0": -0.0, "finite": None}[name]


def _row(name: str, dtype, at: int = 3, n: int = 10) -> np.ndarray:
    rng = np.random.default_rng(at)
    row = rng.standard_normal(n).astype(dtype)
    value = _special(name, dtype)
    if value is not None:
        row[at] = value
    return row


def _device_says(x: np.ndarray) -> np.ndarray:
    return np.asarray(jnp.isfinite(jnp.asarray(x)).all(axis=-1))


@pytest.mark.parametrize("special", SPECIALS)
@pytest.mark.parametrize("dtype", DTYPES, ids=("float32", "bfloat16"))
def test_all_finite_row_agrees_with_device(dtype, special):
    row = _row(special, dtype)
    assert row.dtype == np.dtype(dtype)
    got = all_finite(row)
    assert got.shape == ()
    assert bool(got) == bool(_device_says(row))
    assert bool(got) == (special not in ("nan", "+inf", "-inf"))


@pytest.mark.parametrize("dtype", DTYPES, ids=("float32", "bfloat16"))
def test_all_finite_wave_agrees_with_device(dtype):
    wave = np.stack([_row(s, dtype, at=i % 10)
                     for i, s in enumerate(SPECIALS * 2)])
    got = all_finite(wave)
    assert got.shape == (len(SPECIALS) * 2,)
    np.testing.assert_array_equal(got, _device_says(wave))
    np.testing.assert_array_equal(
        got, [bool(all_finite(r)) for r in wave])


def _zoo(**kw) -> ModelZooServer:
    return ModelZooServer(
        build_zoo(["alexnet"], seed=0, in_res={"alexnet": RES},
                  width_mult=WIDTH, max_batch=4),
        recovery=RecoveryConfig(max_retries=0), **kw)


def _serve(zoo: ModelZooServer, n: int):
    rng = np.random.default_rng(0)
    reqs = [ZooRequest(uid=i, model="alexnet",
                       image=rng.standard_normal((RES, RES, 3))
                       .astype(np.float32)) for i in range(n)]
    for r in reqs:
        zoo.submit(r)
    return reqs, zoo.serve()


@pytest.fixture
def host_only_guard(monkeypatch):
    """Make ``jnp.asarray`` and ``jnp.isfinite`` raise while, and only
    while, ``ModelZooServer._guard`` runs."""
    guard = ModelZooServer._guard

    def device_call(*_, **__):
        raise AssertionError("the integrity guard made a device call")

    def host_only(self, *args):
        with monkeypatch.context() as m:
            m.setattr(jnp, "asarray", device_call)
            m.setattr(jnp, "isfinite", device_call)
            return guard(self, *args)

    monkeypatch.setattr(ModelZooServer, "_guard", host_only)


def test_zoo_guard_decides_on_the_host(host_only_guard):
    clean_reqs, clean = _serve(_zoo(), 4)
    assert all(r.status == "served" for r in clean_reqs)

    key = ("zoo.guard_rows", "zoo.guard")
    before = telemetry.counts().get(key, 0)
    reqs, rep = _serve(_zoo(faults=FaultInjector(
        ChaosConfig(seed=0, corrupt_rate=1.0, corrupt_frac=0.5))), 4)
    executed = sum(d.batch for d in rep.decisions)
    assert executed == 4
    assert telemetry.counts().get(key, 0) - before == executed

    injected = {u for e in rep.events if e.kind == "corrupt"
                for u in e.uids}
    assert injected and len(injected) < 4
    for r, c in zip(reqs, clean_reqs):
        if r.uid in injected:
            assert r.status == "quarantined"
            assert isinstance(r.error, CorruptOutputError)
            assert r.logits is None
        else:
            assert r.status == "served"
            assert r.logits.dtype == c.logits.dtype
            assert r.logits.tobytes() == c.logits.tobytes()


@pytest.mark.parametrize("dtype", DTYPES, ids=("float32", "bfloat16"))
def test_zoo_guard_quarantines_genuine_non_finite_rows(host_only_guard,
                                                       dtype):
    """No fault injected: a row the executor itself returns non-finite
    is refused, in either logits dtype, and its wave-mates are served."""
    zoo = _zoo()
    srv = zoo.models["alexnet"].server
    step_wave = srv.step_wave

    def poisoned():
        done = step_wave()
        for k, c in enumerate(done):
            c.logits = np.asarray(c.logits).astype(dtype)
            if k == 1:
                c.logits[-1] = np.nan
        return done

    srv.step_wave = poisoned
    key = ("zoo.guard_rejects", "zoo.guard")
    before = telemetry.counts().get(key, 0)
    reqs, rep = _serve(zoo, 3)
    assert [r.status for r in reqs] == ["served", "quarantined", "served"]
    assert isinstance(reqs[1].error, CorruptOutputError)
    assert reqs[1].logits is None
    assert all(r.logits.dtype == np.dtype(dtype) for r in (reqs[0], reqs[2]))
    assert telemetry.counts().get(key, 0) - before == 1
    assert any(e.kind == "quarantine" and e.uids == (1,)
               and "genuine" in e.detail for e in rep.events)
