"""Implicit-GEMM SA-CONV — convolution on the systolic dataflow without a
materialized im2col patch matrix.

Paper mapping (Fig. 5 loop nest + Fig. 7B/C):

* The paper's *input-buffer address generator* walks the (P, Q) patch
  window over the on-chip activation slab; weights stay stationary in the
  array.  Here the grid index maps land one whole ``(h, w, bi)`` NHWC input
  slab in VMEM per step and the kernel body walks it one output row at a
  time, loading the P*Q shifted ``(ow, bi)`` views of that row straight
  from the slab ref, each feeding an ``(ow, bi) @ (bi, bj)`` MXU
  contraction.
* Input activations therefore cross HBM once per output-channel tile pass
  — never once per patch element.  The old path materialized the
  ``(batch*oh*ow, p*q*ci)`` patch matrix in HBM (a kernel-area-times input
  blowup the planner never saw); this kernel deletes it.
* A strided conv runs as the stride-1 conv over its phase-split operands
  (space-to-depth, :func:`repro.core.dataflow.conv_phase_dims`), so every
  view is a unit-stride window — the TPU compiler refuses strided value
  slices.
* psum flows down the grid's innermost input-channel dimension into a fp32
  VMEM accumulator (the accumulation-unit SPM of Fig. 7E, kept as
  128-lane blocks of output channels), flushed through
  the fused scale+bias+activation epilogue exactly once per output tile
  (the paper's operator reordering).
* int8 filters (the paper's 8-bit fixed point) ride the same epilogue: the
  int8 tile widens on-chip and the per-output-channel dequant scale
  multiplies the accumulator at flush — HBM moves 1 byte/weight.

Grid order is (batch, co-tiles, ci-tiles) with the contraction innermost
("arbitrary") so the accumulator never spills — the output-stationary
schedule the paper uses for CONV.  A map whose whole slab overflows VMEM
runs in row bands, grid (batch, bands, co-tiles, ci-tiles): each step
holds one band's input rows plus its halo and walks the band's output
rows with the same row loop, so a band changes which rows are resident,
never how a row is computed.  Block shapes come from
:func:`repro.core.dataflow.plan_conv`; the executed tiles ARE the planned
tiles (no clamping between plan and execution).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dataflow import ConvPlan, conv_phase_dims, plan_conv
from repro.kernels import ref
from repro.kernels.geometry import (conv_geometry, interpret_mode,
                                    mxu_precision)


def _implicit_conv_kernel(x_ref, f_ref, *rest, oh: int, ow: int,
                          act: str, has_bias: bool, has_scale: bool,
                          fuse_taps: bool, k_axis: int, pool_window: int = 0,
                          pool_stride: int = 0):
    # oh: the output rows of the resident slab (one band's when banded);
    # k_axis: the grid's innermost (input-channel) axis
    rest = list(rest)
    s_ref = rest.pop(0) if has_scale else None
    b_ref = rest.pop(0) if has_bias else None
    o_ref, acc_ref = rest
    kk = pl.program_id(k_axis)
    p, q, bi, bj = f_ref.shape
    nb, _, _, lb = acc_ref.shape          # bj split into lb-lane blocks

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cdt = x_ref.dtype
    prec = mxu_precision(cdt)
    if fuse_taps:
        # f_ref flattens (p, q, bi) in the same dp-major, dq, bi order the
        # patch row is assembled in (widened first: a narrow dtype's
        # sublane tile need not divide bi)
        w_tile = f_ref[...].astype(cdt).reshape(p * q * bi, bj)

    # a banded slab may be wider than the channel tile (lane padding)
    chans = slice(None) if x_ref.shape[3] == bi else pl.ds(0, bi)

    def row(r, carry):
        # The address generator: the P*Q shifted views of the resident
        # slab that feed output row r — never a patch matrix in HBM.
        views = [x_ref[0, r + dp, pl.ds(dq, ow), chans]
                 for dp in range(p) for dq in range(q)]
        if fuse_taps:
            # one (ow, p*q*bi) patch row, one MXU pass; the planner
            # charged it to the plan's vmem_bytes (ConvPlan.fuse_taps)
            patch = jnp.concatenate(views, axis=1)
            acc = jnp.dot(patch, w_tile, preferred_element_type=jnp.float32,
                          precision=prec)
        else:
            # tap-wise streaming: one view live at a time (the literal
            # per-PE dataflow)
            acc = jnp.zeros((ow, bj), jnp.float32)
            for t, v in enumerate(views):
                acc += jnp.dot(v, f_ref[t // q, t % q].astype(cdt),
                               preferred_element_type=jnp.float32,
                               precision=prec)
        for c in range(nb):
            acc_ref[c, r] += acc[:, c * lb:(c + 1) * lb]
        return carry

    jax.lax.fori_loop(0, oh, row, 0)

    def epilogue(t, c):
        lanes = slice(c * lb, (c + 1) * lb)
        if has_scale:
            t = t * s_ref[:, lanes].astype(jnp.float32)
        if has_bias:
            t = t + b_ref[:, lanes].astype(jnp.float32)
        return t

    @pl.when(kk == pl.num_programs(k_axis) - 1)
    def _flush():
        if pool_window:
            # The pooling-&-activation unit sits right after accumulation
            # (paper Fig. 7): each pooled row is the max of window^2
            # strided row loads from the resident accumulator, and only
            # the POOLED block is emitted — the full OFM never leaves
            # VMEM, and the activation runs once per *pooled* element (the
            # paper's operator reordering, monotone acts only — the
            # planner guarantees it).  A banded plan pools within its
            # band: the planner fuses there only windows that tile it.
            poh, pow_ = o_ref.shape[1], o_ref.shape[2]

            def pooled_row(i, carry):
                for c in range(nb):
                    pooled = None
                    for dp in range(pool_window):
                        for dq in range(pool_window):
                            v = epilogue(acc_ref[c, i * pool_stride + dp,
                                                 pl.ds(dq, pow_,
                                                       stride=pool_stride),
                                                 :], c)
                            pooled = v if pooled is None \
                                else jnp.maximum(pooled, v)
                    o_ref[0, i, :, c * lb:(c + 1) * lb] = ref.apply_act(
                        pooled, act).astype(o_ref.dtype)
                return carry

            jax.lax.fori_loop(0, poh, pooled_row, 0)
        else:
            for c in range(nb):
                o_ref[0, :, :, c * lb:(c + 1) * lb] = ref.apply_act(
                    epilogue(acc_ref[c], c), act).astype(o_ref.dtype)


def _phase_split(x: jax.Array, f: jax.Array, stride: int, bi: int
                 ) -> tuple[jax.Array, jax.Array]:
    """Space-to-depth: the stride-``s`` conv of ``x`` by ``f`` as the
    stride-1 conv of ``x'`` by ``f'``, with
    ``x'[n, i, j, (g, a, b, c)] = x[n, i*s + a, j*s + b, g*bi + c]`` and
    ``f'[u, v, (g, a, b, c), o] = f[u*s + a, v*s + b, g*bi + c, o]``
    (zero past the filter's edge).  Grouping the phases inside each
    ``bi``-channel tile ``g`` keeps the grid's input-channel tiling intact:
    tile ``g`` of ``x'`` is ``s*s*bi`` channels wide."""
    n, h, w, cp = x.shape
    p, q, _, cop = f.shape
    s, g = stride, cp // bi
    hh, ww, pp, qq = conv_phase_dims(h, w, p, q, s)
    x = x[:, :s * hh, :s * ww]
    x = jnp.pad(x, ((0, 0), (0, s * hh - x.shape[1]),
                    (0, s * ww - x.shape[2]), (0, 0)))
    x = x.reshape(n, hh, s, ww, s, g, bi).transpose(0, 1, 3, 5, 2, 4, 6)
    f = jnp.pad(f, ((0, s * pp - p), (0, s * qq - q), (0, 0), (0, 0)))
    f = f.reshape(pp, s, qq, s, g, bi, cop).transpose(0, 2, 4, 1, 3, 5, 6)
    return (x.reshape(n, hh, ww, g * s * s * bi),
            f.reshape(pp, qq, g * s * s * bi, cop))


@functools.partial(jax.jit, static_argnames=("stride", "act", "plan",
                                             "out_dtype", "vmem_limit"))
def sa_conv_implicit(x: jax.Array, f: jax.Array,
                     bias: jax.Array | None = None, *,
                     stride: int = 1, act: str = "none",
                     plan: ConvPlan | None = None,
                     w_scale: jax.Array | None = None,
                     out_dtype=None,
                     vmem_limit: int | None = None) -> jax.Array:
    """NHWC x HWIO VALID conv [+ scale, bias, act] — implicit-GEMM SA-CONV.

    x: (batch, h, w, ci);  f: (p, q, ci, co)  ->  (batch, oh, ow, co).
    ``x`` must already carry any explicit zero padding (the engine applies
    it).  ``f`` may be int8 with ``w_scale`` (co,) per-output-channel
    scales; dequantization fuses into the accumulator-flush epilogue.
    The block shapes are the ones :func:`repro.core.dataflow.plan_conv`
    chose; ``vmem_limit`` (bytes) is the on-chip budget the plan was made
    under, handed to the compiler.  The kernel runs compiled on a TPU and
    in the Pallas interpreter elsewhere
    (:func:`repro.kernels.geometry.interpret_mode`).

    When ``plan.fuse_pool`` is set the flush epilogue additionally reduces
    the ``plan.pool_window``/``plan.pool_stride`` maxpool windows over the
    accumulator tile and the kernel returns the *pooled*
    ``(batch, poh, pow, co)`` map — equal (bitwise, monotone acts) to
    ``maxpool(act(conv(x)))`` without the full OFM ever touching HBM.
    """
    batch, h, w, ci = x.shape
    p, q, ci2, co = f.shape
    assert ci == ci2, (x.shape, f.shape)
    oh = (h - p) // stride + 1
    ow = (w - q) // stride + 1
    out_dtype = out_dtype or x.dtype
    if plan is None:
        plan = plan_conv(batch, h, w, ci, p, q, co, stride=stride,
                         bytes_in=x.dtype.itemsize,
                         bytes_w=f.dtype.itemsize)
    has_bias = bias is not None
    has_scale = w_scale is not None

    # Single source of launch-shape truth: the same geometry object the
    # static verifier (repro.analysis) checks is what gets launched.
    geom = conv_geometry(batch, h, w, ci, p, q, co, stride=stride,
                         plan=plan, has_scale=has_scale, has_bias=has_bias)
    *_, gj, gi = geom.grid
    bi, bj = plan.bi, plan.bj
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, gi * bi - ci))) \
        if gi * bi != ci else x
    fp = jnp.pad(f, ((0, 0), (0, 0), (0, gi * bi - ci), (0, gj * bj - co))) \
        if (gi * bi != ci or gj * bj != co) else f
    if stride > 1:
        xp, fp = _phase_split(xp, fp, stride, bi)
    if plan.bands > 1:
        # a ragged last band reads zero rows past the map (its outputs
        # are dropped below), and a narrow slab is padded to its block's
        # lane tile: every window must lie inside the operand
        xb = geom.input("x").block
        rows = (plan.bands - 1) * plan.band + xb[1]
        xp = jnp.pad(xp, ((0, 0), (0, max(0, rows - xp.shape[1])), (0, 0),
                          (0, gi * xb[3] - xp.shape[3])))

    args = [xp, fp]
    if has_scale:
        args.append(jnp.pad(w_scale.reshape(1, co).astype(jnp.float32),
                            ((0, 0), (0, gj * bj - co))))
    if has_bias:
        args.append(jnp.pad(bias, (0, gj * bj - co)).reshape(1, gj * bj))

    out = pl.pallas_call(
        functools.partial(_implicit_conv_kernel, oh=geom.scratch[0][1],
                          ow=ow, act=act, has_bias=has_bias,
                          has_scale=has_scale, fuse_taps=plan.fuse_taps,
                          k_axis=len(geom.grid) - 1,
                          pool_window=plan.pool_window if plan.fuse_pool
                          else 0,
                          pool_stride=plan.pool_stride),
        grid=geom.grid,
        in_specs=[pl.BlockSpec(tuple(map(pl.Element, s.block))
                               if s.element else s.block, s.index_map)
                  for s in geom.inputs],
        out_specs=pl.BlockSpec(geom.out.block, geom.out.index_map),
        out_shape=jax.ShapeDtypeStruct(geom.out_shape, out_dtype),
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in geom.scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=geom.dimension_semantics,
            vmem_limit_bytes=vmem_limit),
        interpret=interpret_mode(),
    )(*args)
    if plan.bands > 1:                  # drop the ragged band's extra rows
        out = out[:, :oh // plan.pool_stride if plan.fuse_pool else oh]
    return out[..., :co]
