"""CONV layers through the SA-CONV array (paper Fig. 5 loop nest).

MPNA executes convolution on the systolic array by mapping the
(I x P x Q) contraction onto the K rows and the J output channels onto the
L columns — i.e., convolution as GEMM.  The production path is the
*implicit-GEMM* kernel (:mod:`repro.kernels.sa_conv_implicit`): patch
extraction happens inside the kernel via the grid index maps (the paper's
input-buffer address generator), so no im2col patch matrix ever touches
HBM.  Dispatch, planning and tracing live in
:meth:`repro.core.engine.Engine.conv2d`.

This module keeps two things:

* :func:`conv2d_mpna` — a deprecation shim over the current engine's
  ``conv2d`` so old call sites keep working (and now respect the ambient
  engine's :class:`~repro.core.engine.DispatchPolicy`/trace/schedule,
  which the old free function ignored).
* :func:`conv2d_im2col` — the legacy materialized-im2col path, retained
  ONLY as a reference point for benchmarks (`benchmarks/kernel_bench.py`
  measures the traffic/wall-time gap it loses by).  Not used by any model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.sa_conv import sa_conv_matmul


def conv2d_mpna(x: jax.Array, f: jax.Array,
                bias: jax.Array | None = None, *,
                stride: int = 1, act: str = "none") -> jax.Array:
    """Deprecated shim: ``current().conv2d(...)`` on the pallas backend.

    x: (N, H, W, I);  f: (P, Q, I, J)  ->  (N, OH, OW, J), VALID.
    Runs the implicit-GEMM SA-CONV kernel under the ambient engine's
    policy/trace/schedule.  Prefer :meth:`Engine.conv2d`.
    """
    from repro.core import engine
    eng = engine.current().with_(backend="pallas")
    return eng.conv2d(x, f, bias, stride=stride, act=act, name="conv2d_mpna")


@functools.partial(jax.jit, static_argnames=("stride", "act"))
def conv2d_im2col(x: jax.Array, f: jax.Array,
                  bias: jax.Array | None = None, *,
                  stride: int = 1, act: str = "none") -> jax.Array:
    """Legacy materialized-im2col CONV — benchmark reference only.

    Materializes the (N*OH*OW, I*P*Q) patch matrix in HBM (a kernel-area-
    times input blowup) before the GEMM; the implicit-GEMM kernel exists
    to delete exactly this.
    """
    n, h, w, i = x.shape
    p, q, i2, j = f.shape
    assert i == i2
    oh, ow = (h - p) // stride + 1, (w - q) // stride + 1

    patches = jax.lax.conv_general_dilated_patches(
        x, (p, q), (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # conv_general_dilated_patches yields feature order (I, P, Q) flattened
    lhs = patches.reshape(n * oh * ow, i * p * q)
    rhs = jnp.transpose(f, (2, 0, 1, 3)).reshape(i * p * q, j)

    out = sa_conv_matmul(lhs, rhs, bias, act=act)
    return out.reshape(n, oh, ow, j)
