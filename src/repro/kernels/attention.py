"""Blocked (flash) attention Pallas kernel — causal, GQA, sliding-window,
logit-softcap.

This is the transformer-era instance of the paper's dataflow discipline:
the (sq, skv) score matrix is never materialized in HBM; K/V tiles stream
through VMEM while the output tile + online-softmax statistics stay
resident (the accumulation-unit pattern), and fully-masked K/V blocks are
skipped at grid level (the dataflow planner deciding which tiles need to
move at all — for gemma-style sliding-window layers this is what makes the
cost O(s * window) instead of O(s^2)).

Layout inside the kernel: q (1, bq, d), k/v (1, bkv, d); grid
(batch*q_heads, q_blocks, kv_blocks), kv innermost.  GQA is folded into the
K/V index maps (query head h reads kv head h // group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.geometry import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  bq: int, bkv: int, sq: int, skv: int):
    """sq/skv are the TRUE (unpadded) lengths; padded tail keys are masked."""
    iq = pl.program_id(1)
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- block-level skip (dataflow planning: don't touch masked tiles) ---
    offset = skv - sq                      # decode: queries sit at the end
    q_lo = iq * bq + offset
    q_hi = q_lo + bq - 1
    k_lo = ikv * bkv
    k_hi = k_lo + bkv - 1
    live = k_lo <= skv - 1                 # not entirely padding
    if causal:
        live &= k_lo <= q_hi               # not entirely in the future
    if window > 0:
        live &= k_hi > q_lo - window       # not entirely beyond the window

    @pl.when(live)
    def _update():
        q = q_ref[0].astype(jnp.float32)                     # (bq, d)
        k = k_ref[0].astype(jnp.float32)                     # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = kpos < skv                   # padded tail keys are dead
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # (bq, 128)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)           # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])        # (bq, 1)
        p = jnp.exp(s - m_new[:, :1])
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev[:, :1] + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ikv == pl.num_programs(2) - 1)
    def _flush():
        l = l_ref[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bkv"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None,
                    bq: int = 128, bkv: int = 128) -> jax.Array:
    """q: (b, sq, hq, d); k/v: (b, skv, hkv, d) -> (b, sq, hq, d)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = float(scale if scale is not None else d ** -0.5)

    bq = min(bq, max(16, ((sq + 15) // 16) * 16))
    bkv = min(bkv, max(128, ((skv + 127) // 128) * 128))
    sq_p = pl.cdiv(sq, bq) * bq
    skv_p = pl.cdiv(skv, bkv) * bkv
    dp = ((d + 127) // 128) * 128

    # (b*h, s, d) layout; zero-pad seq + head_dim
    qt = jnp.pad(jnp.transpose(q, (0, 2, 1, 3)).reshape(b * hq, sq, d),
                 ((0, 0), (0, sq_p - sq), (0, dp - d)))
    kt = jnp.pad(jnp.transpose(k, (0, 2, 1, 3)).reshape(b * hkv, skv, d),
                 ((0, 0), (0, skv_p - skv), (0, dp - d)))
    vt = jnp.pad(jnp.transpose(v, (0, 2, 1, 3)).reshape(b * hkv, skv, d),
                 ((0, 0), (0, skv_p - skv), (0, dp - d)))

    def kv_head(bh):
        return (bh // hq) * hkv + (bh % hq) // g

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bkv=bkv, sq=sq, skv=skv)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sq_p // bq, skv_p // bkv),
        in_specs=[
            pl.BlockSpec((1, bq, dp), lambda bh, iq, ikv: (bh, iq, 0)),
            pl.BlockSpec((1, bkv, dp), lambda bh, iq, ikv: (kv_head(bh), ikv, 0)),
            pl.BlockSpec((1, bkv, dp), lambda bh, iq, ikv: (kv_head(bh), ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dp), lambda bh, iq, ikv: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom
            pltpu.VMEM((bq, dp), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(qt, kt, vt)
    out = out[:, :sq, :d].reshape(b, hq, sq, d)
    return jnp.transpose(out, (0, 2, 1, 3))
