"""Compile the serving path's kernels for a described TPU v5e.

The CPU runs the Pallas kernels in the interpreter, which accepts block
shapes, strided slices and working sets the chip's compiler refuses.
These tests lower AlexNet's conv1-conv5 (with their fused pools) and
fc1-fc3 at full width, fp32 and int8, at batch 1 and at the zoo's planner
micro-batch, its pools as standalone kernels, and one ``data=4``
cooperative wave on a described 2x2 mesh,
through the TPU compiler — no chip needed, nothing executed.  Each
compiled program must carry a ``tpu_custom_call``: an interpreted
lowering cannot pass.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU compiler library.
"""
from __future__ import annotations

import base64
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core.accelerator import TPU_V5E
from repro.core.dataflow import PlanError, PoolSpec
from repro.core.engine import Engine
from repro.core.quant import QTensor, quantize_cnn_params
from repro.core.schedule import LayerSchedule
from repro.models import cnn
from repro.serve.cnn_server import CNNServer


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the kernels' execution-mode decision to the chip (it asks
    ``jax.default_backend()``) with the persistent compilation cache off:
    a program compiled for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()              # drop traces made in interpret mode
    yield Engine(backend="pallas", chip=TPU_V5E)
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _params(int8: bool):
    """Full-width AlexNet parameter shapes (nothing materialized)."""
    def build():
        p = cnn.init_cnn("alexnet", jax.random.PRNGKey(0))
        return quantize_cnn_params(p) if int8 else p
    return jax.eval_shape(build)


def _microbatch() -> int:
    """The zoo's wave size for AlexNet: the planner's resident FC batch
    tile, capped at the zoo's default admission cap."""
    srv = CNNServer("alexnet", _params(False), max_batch=8,
                    engine=Engine(backend="pallas", chip=TPU_V5E))
    return srv.preferred_microbatch


MICRO = _microbatch()


def _layers():
    """(name, spec, input shape (h, w, c), following pool) per AlexNet
    layer, from the network table."""
    spec, _ = cnn.NETWORKS["alexnet"]
    stats = {l.name: l for l in cnn.network_stats("alexnet")}
    out, ci, fi = [], 0, 0
    for i, s in enumerate(spec):
        if s.kind == "conv":
            ci += 1
            name = f"conv{ci}"
            nxt = spec[i + 1] if i + 1 < len(spec) else None
            pool = PoolSpec(nxt.kernel, nxt.stride) \
                if nxt is not None and nxt.kind == "pool" else None
            out.append((name, s, stats[name].ifm, pool))
        elif s.kind == "fc":
            fi += 1
            name = f"fc{fi}"
            out.append((name, s, stats[name].ifm, None))
    return out


LAYERS = {name: (s, ifm, pool) for name, s, ifm, pool in _layers()}
CASES = [(name, dt, b) for name in LAYERS for dt in ("fp32", "int8")
         for b in (1, MICRO)]


def _weight_args(shape, int8: bool, sharding):
    w = jax.ShapeDtypeStruct(shape, jnp.int8 if int8 else jnp.float32,
                             sharding=sharding)
    if not int8:
        return (w,)
    return w, jax.ShapeDtypeStruct((shape[-1],), jnp.float32,
                                   sharding=sharding)


def _assert_compiled(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()


def _mosaic_matmuls(lowered) -> list[str]:
    """Every MXU contraction in the Mosaic kernels of a lowered program:
    each ``tpu_custom_call`` carries its kernel as base64 MLIR bytecode."""
    from jax._src.lib.mlir import ir

    ops = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                           lowered.as_text()):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            text = str(ir.Module.parse(base64.b64decode(body)))
        ops += [line for line in text.splitlines() if "tpu.matmul" in line]
    return ops


@pytest.mark.parametrize("name,dtype,batch", CASES)
def test_alexnet_layer_compiles_for_v5e(name, dtype, batch, one_chip,
                                        on_tpu):
    eng, int8 = on_tpu, dtype == "int8"
    s, (h, w, c), pool = LAYERS[name]

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def layer(x, bias, *wargs):
        wt = QTensor(*wargs) if int8 else wargs[0]
        if s.kind == "conv":
            return eng.conv2d(x, wt, bias, stride=s.stride, pad=s.pad,
                              act=s.act, pool=pool, name=name)
        return eng.matmul(x, wt, bias, act=s.act, name=name)

    if s.kind == "conv":
        x = S((batch, h, w, c))
        wshape = (s.kernel, s.kernel, c, s.out_ch)
    else:
        x = S((batch, c))
        wshape = (c, s.out_ch)
    args = (x, S((s.out_ch,))) + _weight_args(wshape, int8, one_chip)
    lowered = jax.jit(layer).lower(*args)
    _assert_compiled(lowered.compile())
    # fp32 activations (int8 filters widen to them) contract at fp32
    # precision: the compiler's default is a single bf16 pass
    matmuls = _mosaic_matmuls(lowered)
    assert matmuls
    assert all("contract_precision<fp32>" in op for op in matmuls), matmuls


#: conv name -> (its output map (h, w, c), the maxpool that follows it)
POOLS = {name: (l.ofm, LAYERS[name][2])
         for l in cnn.network_stats("alexnet")
         if (name := l.name) in LAYERS and LAYERS[name][2] is not None}


@pytest.mark.parametrize("after", sorted(POOLS))
def test_alexnet_standalone_pool_compiles_for_v5e(after, one_chip, on_tpu):
    """The unfused pool path (a plan that declines the fused epilogue):
    the standalone maxpool+activation kernel over each AlexNet map a pool
    follows, at the planner micro-batch."""
    eng = on_tpu
    (h, w, c), pool = POOLS[after]
    x = jax.ShapeDtypeStruct((MICRO, h, w, c), jnp.float32, sharding=one_chip)
    lowered = jax.jit(lambda v: eng.pool(
        v, window=pool.window, stride=pool.stride, act="relu")).lower(x)
    _assert_compiled(lowered.compile())


def test_sharded_wave_compiles_on_four_chip_mesh(topo, on_tpu):
    """The fleet's cooperative wave: the whole forward under
    ``shard_map`` over ``("data",)`` on a 2x2 mesh, each device holding
    one micro-batch of rows and a replica of the parameters.  Without the
    map the compiler would have to partition the Pallas kernels, which
    it cannot."""
    eng = on_tpu
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    rep = NamedSharding(mesh, PartitionSpec())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        _params(False))
    x = jax.ShapeDtypeStruct(
        (4 * MICRO, 227, 227, 3), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec("data")))
    wave = jax.shard_map(
        lambda p, xv: cnn.cnn_forward("alexnet", p, xv, eng=eng),
        mesh=mesh, in_specs=(PartitionSpec(), PartitionSpec("data")),
        out_specs=PartitionSpec("data"), check_vma=False)
    compiled = jax.jit(wave).lower(params, x).compile()
    _assert_compiled(compiled)
    assert compiled.output_shardings.spec == PartitionSpec("data")


def _vgg_convs():
    """(name, spec, input (h, w, c), following pool) per VGG-16 conv."""
    spec, _ = cnn.NETWORKS["vgg16"]
    stats = {l.name: l for l in cnn.network_stats("vgg16")}
    out, n = {}, 0
    for i, s in enumerate(spec):
        if s.kind == "conv":
            n += 1
            nxt = spec[i + 1]
            pool = PoolSpec(nxt.kernel, nxt.stride) \
                if nxt.kind == "pool" else None
            out[f"conv{n}"] = (s, stats[f"conv{n}"].ifm, pool)
    return out


VGG_CONVS = _vgg_convs()


@pytest.mark.parametrize("name", sorted(VGG_CONVS, key=lambda n: int(n[4:])))
def test_vgg16_conv_compiles_for_v5e(name, one_chip, on_tpu):
    """Full-width VGG-16 at 224x224, fp32, the zoo's wave of 8: conv1 in
    two row bands (its input windows overlap by the halo), conv2 at the
    edge of the budget, the rest on whole slabs — every one one
    ``sa_conv_implicit`` kernel contracting at fp32."""
    eng = on_tpu
    s, (h, w, c), pool = VGG_CONVS[name]

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    with eng.tracing() as tr:
        lowered = jax.jit(lambda x, b, f: eng.conv2d(
            x, f, b, stride=s.stride, pad=s.pad, act=s.act, pool=pool,
            name=name)).lower(S((8, h, w, c)), S((s.out_ch,)),
                              S((s.kernel, s.kernel, c, s.out_ch)))
    plan = tr[0].conv_plan
    assert plan.bands == (2 if name == "conv1" else 1)
    compiled = lowered.compile()
    _assert_compiled(compiled)
    assert compiled.as_text().count("sa_conv_implicit") >= 1
    matmuls = _mosaic_matmuls(lowered)
    assert matmuls
    assert all("contract_precision<fp32>" in op for op in matmuls), matmuls


def test_vgg16_full_width_fails_at_schedule_time():
    """Where even a one-row band of VGG-16's 224x224 stem overflows the
    budget, under the compiler's own VMEM accounting, the planner refuses
    it — loudly, naming the layer, when the schedule is compiled —
    instead of the chip's compiler refusing it (or a serving lane
    quarantining every request)."""
    from repro.core.engine import DispatchPolicy
    with pytest.raises(PlanError) as ei:
        LayerSchedule.compile_cnn("vgg16", batch=1, policy=DispatchPolicy(
            chip=TPU_V5E, vmem_budget=1024 * 1024))
    assert ei.value.op == "conv1"
