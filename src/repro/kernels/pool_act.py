"""Fused MaxPool -> activation Pallas kernel (paper Fig. 7F-I).

The paper's pooling & activation unit applies the activation *after*
MaxPool — valid for monotonically increasing activations (ReLU,
Leaky-ReLU) and cutting activation-function evaluations by the pool window
area.  We implement the same operator reordering as one fused VMEM pass:
each grid step lands an input tile in VMEM and, one pooled row at a time,
reduces the pool windows as the max of ``window**2`` strided row loads from
the tile ref, applies the activation to the *pooled* row, and writes it out
— one HBM read and one (window^2-times smaller) HBM write per element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.geometry import interpret_mode


def _pool_act_kernel(x_ref, o_ref, *, window: int, stride: int, act: str):
    _, oh, ow, _ = o_ref.shape

    def pooled_row(i, carry):
        out = None
        for p in range(window):
            for q in range(window):
                v = x_ref[0, i * stride + p, pl.ds(q, ow, stride=stride), :]
                out = v if out is None else jnp.maximum(out, v)
        o_ref[0, i] = ref.apply_act(out, act).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, oh, pooled_row, 0)


@functools.partial(jax.jit, static_argnames=("window", "stride", "act", "bc",
                                             "vmem_limit"))
def maxpool_act(x: jax.Array, *, window: int = 2, stride: int = 2,
                act: str = "relu", bc: int = 128,
                vmem_limit: int | None = None) -> jax.Array:
    """(N,H,W,C) -> (N,OH,OW,C) fused maxpool+activation.  ``vmem_limit``
    (bytes) is the on-chip budget handed to the compiler.  Runs compiled
    on a TPU and in the Pallas interpreter elsewhere
    (:func:`repro.kernels.geometry.interpret_mode`)."""
    n, h, w, c = x.shape
    oh = (h - window) // stride + 1
    ow = (w - window) // stride + 1
    bc = min(bc, c)
    if c % bc:                                    # pad channels to tile
        # identity element of max for the dtype: -inf for floats, the most
        # negative representable value for ints (0 would beat genuinely
        # all-negative integer lanes)
        lo = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, bc - c % bc)),
                    constant_values=lo)
    cp = x.shape[-1]

    out = pl.pallas_call(
        functools.partial(_pool_act_kernel, window=window, stride=stride,
                          act=act),
        grid=(n, cp // bc),
        in_specs=[pl.BlockSpec((1, h, w, bc), lambda i, j: (i, 0, 0, j))],
        out_specs=pl.BlockSpec((1, oh, ow, bc), lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, cp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret_mode(),
    )(x)
    return out[..., :c]
