"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for ``tests/test_kernels_*`` allclose sweeps and
double as the XLA execution path used under pjit (the Pallas TPU kernels
cannot lower on the CPU backend; see DESIGN.md §3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# matmul family (SA-CONV / SA-FC functional semantics are identical; the
# kernels differ only in dataflow)
# --------------------------------------------------------------------------
#: Accumulator dtype for the sharded-matmul partial sums.  'float32' is the
#: conservative default; the optimized dry-run variant sets 'bfloat16'
#: (per-shard accumulation still runs in f32 inside the MXU; only the
#: cross-shard psum/collective payload is rounded — the standard Megatron
#: bf16-TP trade, §Perf hillclimb #2, halving every TP all-reduce).
_ACCUM = {"dtype": jnp.float32}


def set_accum_dtype(dtype) -> None:
    _ACCUM["dtype"] = jnp.dtype(dtype)


def matmul(x: jax.Array, w: jax.Array, *, out_dtype=None) -> jax.Array:
    """(m,k) @ (k,n) with fp32 (or flagged bf16) accumulation.

    Operands stay in storage dtype (bf16 on the MXU) — casting them to f32
    first would materialize an f32 copy of every weight matrix in HBM
    (observed as the dominant decode byte term in early dry-runs)."""
    out_dtype = out_dtype or x.dtype
    if x.dtype != w.dtype:
        w = w.astype(x.dtype)
    acc_dt = _ACCUM["dtype"] if x.dtype == jnp.bfloat16 else jnp.float32
    acc = jnp.matmul(x, w, preferred_element_type=acc_dt)
    return acc.astype(out_dtype)


def matmul_bias_act(x, w, b=None, act: str = "none", *, out_dtype=None,
                    w_scale=None):
    """Matmul with the fused epilogue (the accumulation-unit -> pooling &
    activation path of the paper, collapsed into one pass).

    The raw accumulator keeps :data:`_ACCUM`'s dtype (so a row-parallel
    psum crosses the wire at that width); the bias/activation epilogue
    still computes in f32 — XLA fuses the widen+add+act into one pass.

    ``w`` may be int8 with ``w_scale`` (1, n) per-output-channel dequant
    scales: the convert fuses into the dot's operand read and the scale
    multiplies the accumulator (the XLA twin of the Pallas kernels'
    fused epilogue — no dequantized weight copy in HBM)."""
    if x.dtype != w.dtype:
        w = w.astype(x.dtype)
    acc_dt = _ACCUM["dtype"] if x.dtype == jnp.bfloat16 else jnp.float32
    acc = jnp.matmul(x, w, preferred_element_type=acc_dt)
    if b is None and act == "none" and w_scale is None:
        return acc.astype(out_dtype or x.dtype)
    out = acc.astype(jnp.float32)
    if w_scale is not None:
        out = out * w_scale.reshape(1, -1).astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    out = apply_act(out, act)
    return out.astype(out_dtype or x.dtype)


def apply_act(x, act: str):
    if act == "none":
        return x
    if act == "relu":
        return jax.nn.relu(x)
    if act == "leaky_relu":
        return jax.nn.leaky_relu(x, negative_slope=0.1)
    if act == "silu":
        return jax.nn.silu(x)
    if act == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(f"unknown act {act!r}")


def gemv(x: jax.Array, w: jax.Array, *, out_dtype=None) -> jax.Array:
    """Batched GEMV: (b,k) @ (k,n) — the SA-FC workload (weight reuse = b)."""
    return matmul(x, w, out_dtype=out_dtype)


# --------------------------------------------------------------------------
# conv2d (the paper's CONV layer, Fig. 5 pseudocode) + maxpool/act reordering
# --------------------------------------------------------------------------
def conv2d(x: jax.Array, f: jax.Array, *, stride: int = 1,
           padding: str = "VALID", out_dtype=None) -> jax.Array:
    """NHWC x HWIO -> NHWC convolution with fp32 accumulation."""
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), f.astype(jnp.float32),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def maxpool2d(x: jax.Array, *, window: int = 2, stride: int = 2) -> jax.Array:
    lo = -np.inf if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).min
    # a host constant, so that under jit too the window reduces by the
    # differentiable max reducer
    return jax.lax.reduce_window(
        x, np.array(lo, x.dtype), jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1), "VALID")


def maxpool_act(x: jax.Array, *, window: int = 2, stride: int = 2,
                act: str = "relu") -> jax.Array:
    """Paper's pooling&activation unit: activation applied AFTER MaxPool
    (valid for monotone activations — Sec. IV-D)."""
    return apply_act(maxpool2d(x, window=window, stride=stride), act)


# --------------------------------------------------------------------------
# attention (causal, GQA, optional sliding window & logit softcap)
# --------------------------------------------------------------------------
def repeat_kv(k: jax.Array, g: int) -> jax.Array:
    """(b, s, hkv, d) -> (b, s, hkv*g, d).  The broadcast fuses into the
    attention einsums and keeps the head axis cleanly shardable over the
    model mesh axis (hkv*g == hq), which GSPMD cannot recover from the
    grouped (hkv, g) layout."""
    if g == 1:
        return k
    b, s, hkv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (b, s, hkv, g, d)).reshape(b, s, hkv * g, d)


def banded_attention(q, k, v, *, window: int, softcap: float = 0.0,
                     scale: float | None = None) -> jax.Array:
    """Sliding-window attention in O(S * 2w): queries are chunked by the
    window; chunk i attends keys of chunks i-1 and i only (every in-window
    key lies there).  Equivalent to attention(window=w) — asserted in
    tests — but never materializes the S x S score matrix, which is what
    makes 32k-seq SWA prefill (mixtral, gemma local layers) memory-viable
    on the XLA path.  q/k/v: (b, s, h, d) with s % window == 0."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    w = window
    nc = s // w
    scale = scale if scale is not None else dh ** -0.5
    kf = repeat_kv(k, g)
    vf = repeat_kv(v, g)
    qc = q.reshape(b, nc, w, hq, dh)
    kc = kf.reshape(b, nc, w, hq, dh)
    vc = vf.reshape(b, nc, w, hq, dh)
    kprev = jnp.pad(kc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    vprev = jnp.pad(vc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    k2 = jnp.concatenate([kprev, kc], axis=2)          # (b, nc, 2w, h, d)
    v2 = jnp.concatenate([vprev, vc], axis=2)
    logits = jnp.einsum("bnqhd,bnkhd->bnhqk", qc, k2,
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    tq = jnp.arange(w)[:, None]
    tk = jnp.arange(2 * w)[None, :]
    mask = (tk > tq) & (tk <= tq + w)                   # causal ∩ window
    first = (jnp.arange(nc) > 0)[:, None, None] | (tk >= w)[None]
    logits = jnp.where((mask[None] & first)[:, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", p.astype(v.dtype), v2,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, hq, dh).astype(q.dtype)


#: q-chunking threshold for full causal attention on the XLA path: above
#: this sequence length the (S,S) score chain is processed in (c,S) strips
_CHUNKED_ATTENTION_MIN_S = 8192
_ATTENTION_Q_CHUNK = 2048


def chunked_attention(q, k, v, *, chunk: int = _ATTENTION_Q_CHUNK,
                      softcap: float = 0.0,
                      scale: float | None = None) -> jax.Array:
    """Causal full attention scanned over query chunks.

    Peak score memory is (c, S) per step instead of (S, S), and the
    softmax elementwise chain touches each strip once — at 32k this cuts
    the attention memory term ~10x on the dry-run (llama3 prefill) while
    remaining exactly equal to the masked full computation.  The Pallas
    flash kernel is the TPU execution path; this is its XLA-lowerable
    twin used under pjit.  q/k/v: (b, s, h, d), s % chunk == 0."""
    b, s, hq, dh = q.shape
    g = hq // k.shape[2]
    scale = scale if scale is not None else dh ** -0.5
    kf = repeat_kv(k, g)
    vf = repeat_kv(v, g)
    nc = s // chunk
    qc = q.reshape(b, nc, chunk, hq, dh)

    kpos = jnp.arange(s)

    def one(i, qi):
        # qi: (b, c, h, d); attends keys [0, (i+1)*chunk)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qi, kf,
                            preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        qpos = i * chunk + jnp.arange(chunk)
        mask = kpos[None, :] <= qpos[:, None]
        logits = jnp.where(mask[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), vf,
                          preferred_element_type=jnp.float32)

    def body(_, xs):
        i, qi = xs
        return None, one(i, qi)

    _, out = jax.lax.scan(body, None,
                          (jnp.arange(nc), jnp.moveaxis(qc, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, hq, dh)
    return out.astype(q.dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int = 0,
              softcap: float = 0.0, scale: float | None = None) -> jax.Array:
    """q: (b, sq, hq, d); k/v: (b, skv, hkv, d).  hq % hkv == 0 (GQA)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    if (causal and window > 0 and sq == skv and sq % window == 0
            and sq >= 2 * window):
        return banded_attention(q, k, v, window=window, softcap=softcap,
                                scale=scale)
    if (causal and window == 0 and sq == skv
            and sq >= _CHUNKED_ATTENTION_MIN_S
            and sq % _ATTENTION_Q_CHUNK == 0):
        return chunked_attention(q, k, v, softcap=softcap, scale=scale)
    scale = scale if scale is not None else d ** -0.5
    # inputs stay in their storage dtype (no materialized f32 copy of the
    # KV tensors — the first gemma3 dry-run streamed the whole cache
    # through an f32 convert); accumulation is f32 via the MXU contract.
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, repeat_kv(k, g),
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(sq)[:, None] + (skv - sq)   # align ends (decode: sq<skv)
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), repeat_kv(v, g),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) — naive sequential oracle
# --------------------------------------------------------------------------
def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, *, init_state: jax.Array | None = None,
        return_state: bool = False):
    """Naive recurrence (the oracle for the chunked kernel/module).

    x:  (batch, seq, heads, head_dim)   — input
    dt: (batch, seq, heads)             — softplus'd step sizes (>0)
    a:  (heads,)                        — negative decay rates (a < 0)
    b:  (batch, seq, state)             — input gates  (shared across heads)
    c:  (batch, seq, state)             — output gates
    state: (batch, heads, head_dim, state)
    y[t] = c[t] . h[t];  h[t] = exp(a*dt[t]) h[t-1] + dt[t] * x[t] b[t]^T
    """
    bt, sq, nh, hd = x.shape
    ns = b.shape[-1]
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    af, bf, cf = a.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32)
    h0 = (jnp.zeros((bt, nh, hd, ns), jnp.float32)
          if init_state is None else init_state.astype(jnp.float32))

    def step(h, t):
        decay = jnp.exp(af[None, :] * dtf[:, t])            # (bt, nh)
        dx = dtf[:, t, :, None] * xf[:, t]                  # (bt, nh, hd)
        upd = dx[..., None] * bf[:, t, None, None, :]       # (bt, nh, hd, ns)
        h = h * decay[..., None, None] + upd
        y = jnp.einsum("bhds,bs->bhd", h, cf[:, t])
        return h, y

    hT, ys = jax.lax.scan(step, h0, jnp.arange(sq))
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)              # (bt, sq, nh, hd)
    if return_state:
        return y, hT
    return y
