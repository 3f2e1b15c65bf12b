"""Launch geometry of the systolic Pallas kernels as inspectable data.

Every ``pallas_call`` in the SA-CONV / SA-FC kernels is fully determined
by a grid, a set of block specs (block shape + grid->block index map),
the dimension semantics, and the fp32 scratch blocks.  This module
computes that geometry as plain data — :class:`KernelGeometry` — from
the same inputs the kernels receive, and the kernels build their
``pl.BlockSpec``/grid arguments *from it*, so there is exactly one
definition of each kernel's launch shape.

That single source of truth is what makes static verification possible:
:mod:`repro.analysis` re-derives grid coverage, VMEM residency, and
write-race freedom from these objects **without executing any kernel**
— the index maps are ordinary Python callables over integer grid
coordinates, so "symbolic evaluation over the grid" is a nested loop.

The normalization rules here are the kernels' exact historical rules
(``sa_fc_matmul`` batch-tile rounding, ``sa_conv_implicit`` pooled
output blocks); a plan whose tiles disagree with the normalized kernel
tiles is a planner/kernel drift bug, and the coverage pass exists to
flag it.
"""
from __future__ import annotations

import functools
import math
from collections.abc import Callable
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.dataflow import ConvPlan, conv_phase_dims

LANE = 128
SUBLANE = 16


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in the interpreter.  The platform
    decides, in this one place: compiled (Mosaic) on a TPU backend,
    interpreted everywhere else (the CPU test runs).  Every kernel wrapper
    asks here at trace time; nothing else picks the mode."""
    return jax.default_backend() != "tpu"


def mxu_precision(dtype) -> jax.lax.Precision | None:
    """The contraction precision a kernel asks of the MXU for operands of
    ``dtype``.  fp32 operands ask for ``HIGHEST``: left at the default,
    the TPU compiler contracts fp32 in a single bf16 pass, and an fp32
    model would serve at bf16 precision.  Narrower operands keep the
    default.  (The interpreter computes in fp32 either way.)"""
    return jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 \
        else None


#: grid coordinates -> block indices, one int per array dimension
IndexMap = Callable[..., tuple[int, ...]]


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class BlockSpecInfo:
    """One operand's block spec: named, so verifier diagnostics can say
    *which* operand's coverage or residency is wrong.  ``element`` marks
    a spec whose index map returns element offsets instead of block
    indices (``pl.Element`` on every dim): overlapping windows, such as a
    conv band and its halo rows."""
    name: str
    block: tuple[int, ...]
    index_map: IndexMap
    element: bool = False

    @property
    def elems(self) -> int:
        return math.prod(self.block)


@dataclass(frozen=True)
class KernelGeometry:
    """The complete launch geometry of one kernel invocation.

    ``out_shape`` is the (padded) array the kernel writes; ``scratch``
    lists the fp32 VMEM scratch blocks (the accumulator SPMs).  Grid
    dimensions marked ``"arbitrary"`` in ``dimension_semantics`` execute
    sequentially (reduction-carrying); ``"parallel"`` dimensions may be
    reordered/parallelized by the compiler, which is exactly why no two
    of their steps may write the same output block."""
    kernel: str                         # 'sa_fc' | 'sa_conv' | 'sa_conv_implicit'
    grid: tuple[int, ...]
    dimension_semantics: tuple[str, ...]
    inputs: tuple[BlockSpecInfo, ...]
    out: BlockSpecInfo
    out_shape: tuple[int, ...]
    scratch: tuple[tuple[int, ...], ...]

    def input(self, name: str) -> BlockSpecInfo:
        for spec in self.inputs:
            if spec.name == name:
                return spec
        raise KeyError(f"{self.kernel} geometry has no input {name!r}; "
                       f"has {[s.name for s in self.inputs]}")

    @property
    def points(self) -> int:
        """Total grid steps (the verifier enumerates them)."""
        return math.prod(self.grid)


# -- index maps (module-level so geometries are comparable/documented) ------

def _im_x_mk(i: int, j: int, kk: int) -> tuple[int, int]:
    return (i, kk)


def _im_w_kn(i: int, j: int, kk: int) -> tuple[int, int]:
    return (kk, j)


def _im_row_n(i: int, j: int, kk: int) -> tuple[int, int]:
    return (0, j)


def _im_out_mn(i: int, j: int, kk: int) -> tuple[int, int]:
    return (i, j)


def _im_conv_x(n_: int, j: int, k_: int) -> tuple[int, int, int, int]:
    return (n_, 0, 0, k_)


def _im_conv_f(n_: int, j: int, k_: int) -> tuple[int, int, int, int]:
    return (0, 0, k_, j)


def _im_conv_row(n_: int, j: int, k_: int) -> tuple[int, int]:
    return (0, j)


def _im_conv_out(n_: int, j: int, k_: int) -> tuple[int, int, int, int]:
    return (n_, 0, 0, j)


def _im_band_x(rows: int, pbi: int, n_: int, b: int, j: int,
               k_: int) -> tuple[int, int, int, int]:
    """Element offsets of band ``b``'s input window: its first row."""
    return (n_, b * rows, 0, k_ * pbi)


def _im_band_f(n_: int, b: int, j: int, k_: int) -> tuple[int, int, int, int]:
    return (0, 0, k_, j)


def _im_band_row(n_: int, b: int, j: int, k_: int) -> tuple[int, int]:
    return (0, j)


def _im_band_out(n_: int, b: int, j: int, k_: int) -> tuple[int, int, int, int]:
    return (n_, b, 0, j)


# -- geometry builders ------------------------------------------------------

def fc_normalize(b: int, n: int, k: int, *, bb: int | None,
                 bn: int, bk: int) -> tuple[int, int, int, int]:
    """``sa_fc_matmul``'s historical tile normalization: padded batch
    ``bp``, and the executed ``(bb, bn, bk)``.  ``bb=None`` keeps the
    whole padded batch resident."""
    bp = max(SUBLANE, _round_up(b, SUBLANE))
    if bb is None:
        bb = bp
    bb = max(SUBLANE, min(_round_up(bb, SUBLANE), bp))
    bn = min(bn, _round_up(n, LANE))
    bk = min(bk, _round_up(k, LANE))
    return bp, bb, bn, bk


def fc_geometry(b: int, n: int, k: int, *, bb: int | None = None,
                bn: int = 512, bk: int = 512,
                has_scale: bool = False,
                has_bias: bool = False) -> KernelGeometry:
    """Launch geometry of :func:`repro.kernels.sa_fc.sa_fc_matmul` for a
    ``(b,k) @ (k,n)`` op — grid ``(batch-tiles, n-tiles, k-tiles)``,
    K innermost-sequential so the ``(bb, bn)`` accumulator never spills."""
    bp, bb, bn, bk = fc_normalize(b, n, k, bb=bb, bn=bn, bk=bk)
    gb, gn, gk = _cdiv(bp, bb), _cdiv(n, bn), _cdiv(k, bk)
    inputs = [BlockSpecInfo("x", (bb, bk), _im_x_mk),
              BlockSpecInfo("w", (bk, bn), _im_w_kn)]
    if has_scale:
        inputs.append(BlockSpecInfo("scale", (1, bn), _im_row_n))
    if has_bias:
        inputs.append(BlockSpecInfo("bias", (1, bn), _im_row_n))
    return KernelGeometry(
        kernel="sa_fc", grid=(gb, gn, gk),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        inputs=tuple(inputs),
        out=BlockSpecInfo("out", (bb, bn), _im_out_mn),
        out_shape=(gb * bb, gn * bn),
        scratch=((bb, bn),))


def matmul_geometry(m: int, n: int, k: int, *, bm: int, bn: int, bk: int,
                    has_scale: bool = False,
                    has_bias: bool = False) -> KernelGeometry:
    """Launch geometry of :func:`repro.kernels.sa_conv.sa_conv_matmul`
    for an ``(m,k) @ (k,n)`` op — output-stationary ``(m, n, k)`` grid,
    K innermost-sequential."""
    gm, gn, gk = _cdiv(m, bm), _cdiv(n, bn), _cdiv(k, bk)
    inputs = [BlockSpecInfo("x", (bm, bk), _im_x_mk),
              BlockSpecInfo("w", (bk, bn), _im_w_kn)]
    if has_scale:
        inputs.append(BlockSpecInfo("scale", (1, bn), _im_row_n))
    if has_bias:
        inputs.append(BlockSpecInfo("bias", (1, bn), _im_row_n))
    return KernelGeometry(
        kernel="sa_conv", grid=(gm, gn, gk),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        inputs=tuple(inputs),
        out=BlockSpecInfo("out", (bm, bn), _im_out_mn),
        out_shape=(gm * bm, gn * bn),
        scratch=((bm, bn),))


def conv_geometry(batch: int, h: int, w: int, ci: int,
                  p: int, q: int, co: int, *, stride: int,
                  plan: ConvPlan,
                  has_scale: bool = False,
                  has_bias: bool = False) -> KernelGeometry:
    """Launch geometry of
    :func:`repro.kernels.sa_conv_implicit.sa_conv_implicit` — grid
    ``(batch, co-tiles, ci-tiles)`` with the input-channel contraction
    innermost-sequential; the output block is the *pooled* map when the
    plan committed the fused maxpool flush epilogue.

    A strided conv launches as the stride-1 conv over its phase-split
    operands (:func:`repro.core.dataflow.conv_phase_dims`): the input
    block is ``(1, h', w', stride**2 * bi)`` and the filter block
    ``(p', q', stride**2 * bi, bj)``.  The fp32 accumulator is one
    ``(bj / lb, oh, ow, lb)`` scratch block, split into ``lb = min(bj,
    128)``-lane blocks: the pooled epilogue's strided row loads need a
    base memref one lane tile wide.

    A banded plan (``plan.bands > 1``) adds a band axis after the batch,
    grid ``(batch, bands, co-tiles, ci-tiles)``: the input block is the
    band's ``plan.band`` rows plus ``p' - 1`` halo rows, an overlapping
    window indexed by element offsets over the input zero-padded to
    ``bands * band + p' - 1`` rows; the accumulator and the output block
    hold one band's (pooled) rows, and ``out_shape`` the rows of every
    band, the ragged last band's extra rows included."""
    oh = (h - p) // stride + 1
    ow = (w - q) // stride + 1
    ooh, oow = oh, ow
    if plan.fuse_pool:
        ooh = (oh - plan.pool_window) // plan.pool_stride + 1
        oow = (ow - plan.pool_window) // plan.pool_stride + 1
    hh, ww, pp, qq = conv_phase_dims(h, w, p, q, stride)
    bi, bj = plan.bi, plan.bj
    gi, gj = _cdiv(ci, bi), _cdiv(co, bj)
    pbi = stride * stride * bi
    lb = min(bj, LANE)
    if plan.bands > 1:
        rows = plan.band
        out_rows = rows // plan.pool_stride if plan.fuse_pool else rows
        # the compiler takes an element window only over whole lane
        # tiles: a narrow slab (the RGB stem, one channel tile) is padded
        # to one, which its VMEM layout holds anyway
        xl = _round_up(pbi, LANE)
        x = BlockSpecInfo("x", (1, rows + pp - 1, ww, xl),
                          functools.partial(_im_band_x, rows, xl),
                          element=True)
        im_f, im_row, im_out = _im_band_f, _im_band_row, _im_band_out
        grid = (batch, plan.bands, gj, gi)
        oh, ooh, out_shape_rows = rows, out_rows, plan.bands * out_rows
    else:
        x = BlockSpecInfo("x", (1, hh, ww, pbi), _im_conv_x)
        im_f, im_row, im_out = _im_conv_f, _im_conv_row, _im_conv_out
        grid = (batch, gj, gi)
        out_shape_rows = ooh
    inputs = [x, BlockSpecInfo("w", (pp, qq, pbi, bj), im_f)]
    if has_scale:
        inputs.append(BlockSpecInfo("scale", (1, bj), im_row))
    if has_bias:
        inputs.append(BlockSpecInfo("bias", (1, bj), im_row))
    return KernelGeometry(
        kernel="sa_conv_implicit", grid=grid,
        dimension_semantics=("parallel",) * (len(grid) - 1)
        + ("arbitrary",),
        inputs=tuple(inputs),
        out=BlockSpecInfo("out", (1, ooh, oow, bj), im_out),
        out_shape=(batch, out_shape_rows, oow, gj * bj),
        scratch=((bj // lb, oh, ow, lb),))
