"""Sharding-rule unit tests on an AbstractMesh (no devices needed — the
rules are pure functions of mesh shape + leaf path)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh

from repro.configs.registry import all_lm_configs
from repro.distributed import sharding as SH
from repro.models import transformer as T

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _specs(cfg, mesh):
    shapes = jax.eval_shape(lambda: T.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return {jax.tree_util.keystr(path):
            (leaf.shape, SH._param_spec(cfg, mesh, path, leaf.shape))
            for path, leaf in flat}


@pytest.mark.parametrize("mesh", [MESH1, MESH2], ids=["1pod", "2pod"])
@pytest.mark.parametrize("arch", sorted(all_lm_configs()))
def test_every_spec_divides(arch, mesh):
    """A PartitionSpec must never ask for a non-dividing shard."""
    cfg = all_lm_configs()[arch]
    for name, (shape, spec) in _specs(cfg, mesh).items():
        for dim, ax in zip(shape, spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (arch, name, shape, spec)


def test_tp_pattern_megatron():
    """qkv/gate/up column-parallel, o/down row-parallel over `model`."""
    cfg = all_lm_configs()["olmo-1b"]
    specs = _specs(cfg, MESH1)
    get = lambda frag: [v for k, v in specs.items() if frag in k][0]
    assert get("wq")[1][-1] == "model"         # column
    assert get("wo")[1][-2 if False else 1] == "model" or \
        get("wo")[1][1] == "model"             # row (stacked: dims 1..2)
    assert get("wd")[1].index("model") < len(get("wd")[1]) - 1


def test_moe_expert_parallel_when_divisible():
    """llama4: 128 experts over 16 shards = EP; mixtral: 8 experts -> TP."""
    l4 = _specs(all_lm_configs()["llama4-maverick-400b-a17b"], MESH1)
    wg = [v for k, v in l4.items()
          if "moe" in k and "'wg'" in k and "shared" not in k][0]
    assert wg[1][1] == "model"                 # (reps, E, d, ff): E sharded
    mx = _specs(all_lm_configs()["mixtral-8x7b"], MESH1)
    wgm = [v for k, v in mx.items()
           if "moe" in k and "'wg'" in k and "shared" not in k][0]
    assert wgm[1][1] is None and "model" in wgm[1]   # ff sharded instead


def test_norms_replicated():
    cfg = all_lm_configs()["gemma2-27b"]
    for name, (shape, spec) in _specs(cfg, MESH1).items():
        if len(shape) <= 2 and "norm" in name:
            assert all(s is None for s in spec), (name, spec)


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 8))
    out = SH.constrain(x, ("dp", "tp"))
    assert out is x


def test_dp_axes_and_sizes():
    assert SH.dp_axes(MESH2) == ("pod", "data")
    assert SH.dp_size(MESH2) == 32
    assert SH.tp_size(MESH1) == 16
