"""Mesh-helper and elastic-replan edge cases: ``dp_axes``/``dp_size``/
``tp_size`` on abstract meshes (no devices needed), and
``elastic.replan``/``degrade_sequence`` boundaries — exact-fit
survivors, non-power-of-two loss, batch-divisibility fallback to
``data=1``, and the typed :class:`InsufficientReplicasError` replacing
the seed-era bare ``assert``."""
from __future__ import annotations

import pytest
from jax.sharding import AbstractMesh

from repro.distributed import sharding as SH
from repro.distributed.elastic import MeshPlan, degrade_sequence, replan
from repro.serve.errors import InsufficientReplicasError, ServeError


# -- dp_axes / dp_size / tp_size ---------------------------------------------

def test_dp_axes_selects_pod_and_data_in_order():
    assert SH.dp_axes(AbstractMesh((4, 8), ("data", "model"))) \
        == ("data",)
    assert SH.dp_axes(AbstractMesh((2, 4, 8),
                                     ("pod", "data", "model"))) \
        == ("pod", "data")
    # a model-only mesh has no data-parallel axes at all
    assert SH.dp_axes(AbstractMesh((8,), ("model",))) == ()


def test_dp_size_multiplies_every_dp_axis():
    assert SH.dp_size(AbstractMesh((4, 8), ("data", "model"))) == 4
    assert SH.dp_size(AbstractMesh((2, 4, 8),
                                     ("pod", "data", "model"))) == 8
    # no dp axes -> the empty product, 1
    assert SH.dp_size(AbstractMesh((8,), ("model",))) == 1


def test_tp_size_defaults_to_one_without_model_axis():
    assert SH.tp_size(AbstractMesh((4, 8), ("data", "model"))) == 8
    assert SH.tp_size(AbstractMesh((4,), ("data",))) == 1


# -- elastic.replan edges ----------------------------------------------------

def test_replan_exact_fit_survivors_waste_nothing():
    """Survivors exactly 2^k * model_parallel: every chip is used."""
    p = replan(32, model_parallel=16, global_batch=256, pod_size=256)
    assert p == MeshPlan(pods=1, data=2, model=16, used_chips=32,
                         wasted_chips=0)
    assert p.shape == (2, 16) and p.axis_names == ("data", "model")


def test_replan_non_power_of_two_loss_wastes_the_remainder():
    """48 survivors hold data=2 (32 chips); the stranded 16 are waste —
    the planner never proposes a ragged data degree."""
    p = replan(48, model_parallel=16, global_batch=256, pod_size=256)
    assert (p.data, p.used_chips, p.wasted_chips) == (2, 32, 16)


def test_replan_batch_divisibility_falls_back_to_data_1():
    """Plenty of chips, but the global batch does not divide by 2: the
    data degree stays 1 no matter how many survivors remain."""
    p = replan(64, model_parallel=16, global_batch=17, pod_size=256)
    assert p.data == 1
    assert p.wasted_chips == 64 - 16


def test_replan_multi_pod_keeps_pod_axis():
    p = replan(512, model_parallel=16, global_batch=256, pod_size=256)
    assert p.pods == 2
    assert p.axis_names == ("pod", "data", "model")
    assert p.shape == (2, p.data, 16)


def test_replan_below_model_parallel_raises_typed_error():
    """The seed-era bare assert is now a typed, attribute-carrying
    error (and survives ``python -O``, which strips asserts)."""
    with pytest.raises(InsufficientReplicasError) as ei:
        replan(8, model_parallel=16)
    assert ei.value.survivors == 8
    assert ei.value.required == 16
    assert isinstance(ei.value, ServeError)
    assert "8 survivor(s)" in str(ei.value)


def test_degrade_sequence_plans_every_event():
    plans = degrade_sequence(64, [16, 16], model_parallel=16,
                             global_batch=256, pod_size=256)
    assert [p.data for p in plans] == [2, 2]
    assert [p.wasted_chips for p in plans] == [16, 0]


def test_degrade_sequence_surfaces_the_breaking_event():
    """When an event drops survivors below the floor, the typed error
    names the event and the loss history, chained from the replan
    error."""
    with pytest.raises(InsufficientReplicasError) as ei:
        degrade_sequence(64, [16, 40], model_parallel=16,
                         global_batch=256, pod_size=256)
    assert "failure event 1" in str(ei.value)
    assert "8 remain of 64" in str(ei.value)
    assert ei.value.survivors == 8 and ei.value.required == 16
    assert isinstance(ei.value.__cause__, InsufficientReplicasError)
