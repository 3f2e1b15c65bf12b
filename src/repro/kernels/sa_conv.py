"""SA-CONV — the weight-stationary systolic dataflow as a Pallas MXU kernel.

Paper mapping (Fig. 7B/C):

* the K x L PE array         -> one (bm, bk) @ (bk, bn) MXU block-matmul
* psum flowing down columns  -> fp32 accumulator tile in VMEM scratch,
                                carried across the K grid dimension
                                (the "accumulation unit" SPM of Fig. 7E)
* the extra per-PE weight register enabling "parallel weight movement"
  (load next tile while current computes) -> Pallas/Mosaic double-buffered
  pipelining of the `w` BlockSpec: iteration (i,j,k+1)'s weight DMA overlaps
  iteration (i,j,k)'s matmul because `w`'s index map only depends on grid
  coordinates, making the prefetch address known one step ahead.
* accumulation-unit -> pooling&activation chain -> fused
  scale+bias+activation epilogue executed once, on the last K step (the
  paper's operator reordering: the epilogue touches each output exactly
  once).  int8 weights ride the same epilogue: the per-output-channel
  dequant scale multiplies the fp32 accumulator at flush, so the weight
  stream stays 1 byte/weight.

Grid order is (m, n, k) with K innermost ("arbitrary") so the accumulator
never spills — the output-stationary schedule the paper uses for CONV.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dataflow import MatmulPlan, plan_matmul
from repro.kernels import ref
from repro.kernels.geometry import (interpret_mode, matmul_geometry,
                                    mxu_precision)


def _epilogue(acc, scale, bias, act: str):
    out = acc if scale is None else acc * scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return ref.apply_act(out, act)


def _sa_conv_kernel(x_ref, w_ref, *rest, act: str, has_bias: bool,
                    has_scale: bool):
    rest = list(rest)
    s_ref = rest.pop(0) if has_scale else None
    b_ref = rest.pop(0) if has_bias else None
    o_ref, acc_ref = rest
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...].astype(x_ref.dtype),
                            preferred_element_type=jnp.float32,
                            precision=mxu_precision(x_ref.dtype))

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        scale = s_ref[...] if has_scale else None
        bias = b_ref[...] if has_bias else None
        o_ref[...] = _epilogue(acc_ref[...], scale, bias,
                               act).astype(o_ref.dtype)


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


@functools.partial(jax.jit, static_argnames=("act", "plan", "out_dtype",
                                             "vmem_limit"))
def sa_conv_matmul(x: jax.Array, w: jax.Array,
                   bias: jax.Array | None = None, *,
                   act: str = "none",
                   plan: MatmulPlan | None = None,
                   w_scale: jax.Array | None = None,
                   out_dtype=None,
                   vmem_limit: int | None = None) -> jax.Array:
    """(m,k) @ (k,n) [+ scale, bias, act] through the SA-CONV dataflow.

    The BlockSpecs are the ones the Case-1..4 planner chose
    (:func:`repro.core.dataflow.plan_matmul`); ``vmem_limit`` (bytes) is
    the on-chip budget handed to the compiler.  The kernel runs compiled
    on a TPU and in the Pallas interpreter elsewhere
    (:func:`repro.kernels.geometry.interpret_mode`).  ``w`` may be int8
    with ``w_scale`` (1, n) per-output-channel scales; dequantization fuses
    into the accumulator-flush epilogue.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    if plan is None:
        plan = plan_matmul(m, n, k, bytes_in=x.dtype.itemsize,
                           bytes_w=w.dtype.itemsize)
    # The planner caps tiles at dataflow.MAX_TILE, so the executed tiling
    # IS the planned tiling — plan.hbm_bytes/vmem_bytes describe this run.
    bm, bn, bk = plan.bm, plan.bn, plan.bk
    has_bias = bias is not None
    has_scale = w_scale is not None

    # Single source of launch-shape truth, shared with the static
    # verifier (repro.analysis): the pallas_call transcribes it.
    geom = matmul_geometry(m, n, k, bm=bm, bn=bn, bk=bk,
                           has_scale=has_scale, has_bias=has_bias)
    gm, gn, gk = geom.grid
    xp = _pad_to(x, gm * bm, gk * bk)
    wp = _pad_to(w, gk * bk, gn * bn)

    args = [xp, wp]
    if has_scale:
        args.append(jnp.pad(w_scale.reshape(1, n).astype(jnp.float32),
                            ((0, 0), (0, gn * bn - n))))
    if has_bias:
        args.append(jnp.pad(bias, (0, gn * bn - n)).reshape(1, gn * bn))

    out = pl.pallas_call(
        functools.partial(_sa_conv_kernel, act=act, has_bias=has_bias,
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
    return out[:m, :n]
