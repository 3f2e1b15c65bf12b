"""SA-FC — the batch-amortized weight-streaming systolic dataflow as a
Pallas kernel.

Paper mapping (Fig. 7D, Fig. 8): FC layers have per-sample weight reuse = 1,
so a weight-stationary array stalls on the K-cycle refill between tiles.
SA-FC adds *dedicated weight buses to every PE* so a fresh K x L weight tile
enters the array every cycle; throughput becomes bound by the weight stream
(DRAM bandwidth).  That stream only pays off when each weight byte is
*amortized across a batch of samples* — which is exactly what this kernel's
grid encodes.

TPU adaptation: in a batched GEMM ``(b,k) @ (k,n)`` with small per-tile
batch, arithmetic intensity ~ 2*bb FLOP/byte << ridge (~240), so the
kernel's job is to stream every weight byte from HBM exactly once **per
batch tile** at full bandwidth while the activation tile and the fp32
accumulator stay VMEM-resident:

* activations ``x`` -> one ``(bb, bk)`` tile resident per (batch, K) step;
  the batch tile ``bb`` is the planner's amortization lever
  (:class:`repro.core.dataflow.FCPlan`) — the whole ``(b, k)`` block is
  *not* forced resident, so serving batch sizes cannot silently blow the
  VMEM budget;
* weights ``w``     -> ``(bk, bn)`` tiles, each visited once per batch
  tile (grid covers the weight matrix bijectively per batch step),
  double-buffered so the next tile's DMA overlaps the current tile's MAC
  — the per-PE weight-bus analogue.  Total weight traffic =
  ``ceil(b/bb) * k * n * itemsize`` bytes: the compulsory minimum when the
  batch fits one tile, the batch-amortized stream otherwise;
* accumulator       -> ``(bb, bn)`` fp32 scratch carried across the K
  dimension (the accumulation-unit SPM), flushed through the fused
  scale+bias+activation epilogue on the last K step.

int8 weights (the paper's 8-bit fixed point): ``w`` may be int8 with a
per-output-channel ``w_scale`` (1, n).  The int8 tile is widened *inside
the kernel* (VMEM -> registers) and the scale multiplies the fp32
accumulator once, at flush — so HBM moves exactly 1 byte/weight/pass and
no dequantized copy of the weight matrix ever exists.

``vmem_limit`` makes the residency claim checkable: the kernel computes
its working set with the same :func:`repro.core.dataflow.fc_vmem_bytes`
the planner budgets with and refuses block shapes that could never be
resident on the modeled hardware (previously nothing stopped a caller
from requesting them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dataflow import fc_vmem_bytes
from repro.kernels import ref
from repro.kernels.geometry import fc_geometry, interpret_mode, mxu_precision


def _sa_fc_kernel(x_ref, w_ref, *rest, act: str, has_bias: bool,
                  has_scale: bool):
    rest = list(rest)
    s_ref = rest.pop(0) if has_scale else None
    b_ref = rest.pop(0) if has_bias else None
    o_ref, acc_ref = rest
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # One streamed weight tile: consumed once per batch tile, never
    # revisited inside it.  int8 tiles widen here, on-chip — the raw int8
    # accumulator is rescaled at flush.
    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...].astype(x_ref.dtype),
                            preferred_element_type=jnp.float32,
                            precision=mxu_precision(x_ref.dtype))

    @pl.when(kk == pl.num_programs(2) - 1)
    def _flush():
        out = acc_ref[...]
        if has_scale:
            out = out * s_ref[...].astype(jnp.float32)
        if has_bias:
            out = out + b_ref[...].astype(jnp.float32)
        o_ref[...] = ref.apply_act(out, act).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "bb", "bn", "bk",
                                             "out_dtype", "vmem_limit"))
def sa_fc_matmul(x: jax.Array, w: jax.Array,
                 bias: jax.Array | None = None, *,
                 act: str = "none",
                 bb: int | None = None,
                 bn: int = 512, bk: int = 512,
                 w_scale: jax.Array | None = None,
                 out_dtype=None,
                 vmem_limit: int | None = None) -> jax.Array:
    """(b,k) @ (k,n) — batch-amortized weight-streaming dataflow.

    Grid is ``(batch-tiles, n-tiles, k-tiles)`` with K innermost: each
    weight tile is read from HBM exactly once per batch tile, so total
    weight traffic is ``ceil(b/bb) * k * n * itemsize`` bytes — the
    planner's (:func:`repro.core.dataflow.plan_fc`) amortized stream.
    ``bb=None`` keeps the whole (padded) batch resident in one tile
    (weights fetched once only, the paper's Fig. 8 semantics — correct
    whenever the batch fits the budget).

    ``w`` may be int8 with ``w_scale`` (1, n) per-output-channel scales;
    dequantization fuses into the accumulator-flush epilogue.

    ``vmem_limit`` (bytes) rejects block shapes whose resident working set
    — activation tile, double-buffered weight tile, fp32 accumulator,
    output tile, per :func:`repro.core.dataflow.fc_vmem_bytes` — exceeds
    the modeled on-chip budget, instead of silently "running" an
    impossible residency in interpret mode; it is also the limit handed to
    the compiler.  The kernel runs compiled on a TPU and in the Pallas
    interpreter elsewhere (:func:`repro.kernels.geometry.interpret_mode`).
    """
    b, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    has_bias = bias is not None
    has_scale = w_scale is not None

    # The launch geometry (grid, block specs, index maps, scratch) is
    # computed once, as data, and verified statically by repro.analysis —
    # the pallas_call below is a straight transcription of it.
    geom = fc_geometry(b, n, k, bb=bb, bn=bn, bk=bk,
                       has_scale=has_scale, has_bias=has_bias)
    gb, gn, gk = geom.grid
    bb, bk = geom.input("x").block
    bn = geom.input("w").block[1]

    if vmem_limit is not None:
        need = fc_vmem_bytes(bb, bn, bk, bytes_in=x.dtype.itemsize,
                             bytes_w=w.dtype.itemsize,
                             bytes_out=jnp.dtype(out_dtype).itemsize)
        if need > vmem_limit:
            raise ValueError(
                f"sa_fc_matmul block (bb={bb}, bn={bn}, bk={bk}) needs "
                f"{need} resident bytes > vmem_limit={vmem_limit}; "
                f"plan smaller tiles (repro.core.dataflow.plan_fc)")

    xp = jnp.pad(x, ((0, gb * bb - b), (0, gk * bk - k)))
    wp = jnp.pad(w, ((0, gk * bk - k), (0, gn * bn - n)))

    args = [xp, wp]
    if has_scale:
        args.append(jnp.pad(w_scale.reshape(1, n).astype(jnp.float32),
                            ((0, 0), (0, gn * bn - n))))
    if has_bias:
        args.append(jnp.pad(bias, (0, gn * bn - n)).reshape(1, gn * bn))

    out = pl.pallas_call(
        functools.partial(_sa_fc_kernel, act=act, has_bias=has_bias,
                          has_scale=has_scale),
        grid=geom.grid,
        in_specs=[pl.BlockSpec(s.block, s.index_map) for s in geom.inputs],
        out_specs=pl.BlockSpec(geom.out.block, geom.out.index_map),
        out_shape=jax.ShapeDtypeStruct(geom.out_shape, out_dtype),
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in geom.scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=geom.dimension_semantics,
            vmem_limit_bytes=vmem_limit),
        interpret=interpret_mode(),
    )(*args)
    return out[:b, :n]
