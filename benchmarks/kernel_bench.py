"""Kernel micro-benchmarks: MPNA dataflow kernels vs. the jnp oracle.

On the CPU Pallas runs in the interpreter, so the wall numbers there
characterize the interpreter, not the chip; on a TPU the same rows time
the compiled kernels.  The derived column reports the
dataflow planner's analytic HBM traffic vs. the compulsory minimum —
the figure of merit the SA-CONV/SA-FC designs optimize.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

try:                                    # package import (benchmarks.run)
    from benchmarks.timing import median_wall_us
except ImportError:                     # direct script execution
    from timing import median_wall_us

Row = tuple[str, float, str]


def _time(fn, *args, reps=5):
    """Median wall microseconds (benchmarks/timing.py shared estimator)."""
    return median_wall_us(lambda: fn(*args), reps=reps, trials=3)


def matmul_planner() -> list[Row]:
    from repro.core.dataflow import compulsory_bytes, plan_matmul
    rows = []
    cases = [("train_proj", 8192, 8192, 8192),
             ("prefill_ffn", 32768, 14336, 4096),
             ("decode_gemv", 8, 8192, 8192),
             ("expert_mm", 2048, 14336, 4096)]
    for name, m, n, k in cases:
        t0 = time.perf_counter()
        p = plan_matmul(m, n, k)
        us = (time.perf_counter() - t0) * 1e6
        cb = compulsory_bytes(m, n, k)
        rows.append((f"planner/{name}", us,
                     f"case{p.case}/{p.regime} traffic={p.hbm_bytes/2**20:.0f}MiB "
                     f"(min {cb/2**20:.0f}MiB, x{p.hbm_bytes/cb:.2f})"))
    return rows


def conv_planner() -> list[Row]:
    """The conv-aware planner on the paper's own layers: analytic HBM
    traffic of the implicit-GEMM schedule (maxpool fused into the flush
    epilogue where the spec has a trailing pool) vs. the compulsory
    minimum vs. the kernel-area blowup the materialized-im2col path
    moved, plus the unfused conv->HBM->pool bytes the fusion deletes."""
    from repro.core.perf_model import pallas_conv_traffic
    rows = []
    for net in ("alexnet", "vgg16"):
        t0 = time.perf_counter()
        layers = pallas_conv_traffic(net, batch=1)
        us = (time.perf_counter() - t0) * 1e6
        for row in layers[:2]:
            p = row.plan
            pooltag = ""
            if p.fuse_pool:
                pooltag = (f"; pool{p.pool_window}s{p.pool_stride} fused, "
                           f"unfused path moved "
                           f"{row.unfused_bytes/2**20:.1f}MiB")
            rows.append((
                f"conv_planner/{net}/{row.layer}", us / len(layers),
                f"case{p.case}/{p.regime} bi={p.bi} bj={p.bj} "
                f"traffic={p.hbm_bytes/2**20:.1f}MiB "
                f"(min {row.compulsory_bytes/2**20:.1f}MiB "
                f"x{p.hbm_bytes/row.compulsory_bytes:.2f}; im2col moved "
                f"{row.im2col_bytes/2**20:.1f}MiB "
                f"x{row.im2col_bytes/p.hbm_bytes:.1f}{pooltag})"))
    return rows


def conv_kernels() -> list[Row]:
    """Implicit-GEMM SA-CONV vs. the deleted materialized-im2col path on an
    AlexNet conv2-shaped layer (27x27x96 -> 256, 5x5, pad 2)."""
    from repro.kernels.conv2d import conv2d_im2col, conv2d_mpna
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 31, 31, 96),
                          jnp.float32)
    f = jax.random.normal(jax.random.PRNGKey(1), (5, 5, 96, 256),
                          jnp.float32) * 0.05
    b = jnp.zeros((256,), jnp.float32)
    return [
        ("kernel/conv_implicit_gemm_interp",
         _time(lambda: conv2d_mpna(x, f, b, act="relu"), reps=3),
         "pallas, patches on-chip"),
        ("kernel/conv_im2col_interp",
         _time(lambda: conv2d_im2col(x, f, b, act="relu"), reps=3),
         "legacy: patch matrix in HBM"),
    ]


def kernels_pallas() -> list[Row]:
    from repro.kernels import ref
    from repro.kernels.sa_conv import sa_conv_matmul
    from repro.kernels.sa_fc import sa_fc_matmul
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 512), jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(2), (8, 2048), jnp.float32)
    ws = jax.random.normal(jax.random.PRNGKey(3), (2048, 1024), jnp.float32)
    rows = [
        ("kernel/sa_conv_256x512x512_interp",
         _time(lambda: sa_conv_matmul(x, w)), "pallas"),
        ("kernel/ref_matmul_256x512x512",
         _time(lambda: ref.matmul(x, w)), "jnp oracle"),
        ("kernel/sa_fc_8x2048x1024_interp",
         _time(lambda: sa_fc_matmul(xs, ws)), "pallas"),
        ("kernel/ref_gemv_8x2048x1024",
         _time(lambda: ref.gemv(xs, ws)), "jnp oracle"),
    ]
    from repro.kernels.attention import flash_attention
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(5), (1, 256, 2, 64), jnp.float32)
    rows.append(("kernel/flash_attn_256_interp",
                 _time(lambda: flash_attention(q, k, k)), "pallas"))
    rows.append(("kernel/ref_attn_256",
                 _time(lambda: ref.attention(q, k, k)), "jnp oracle"))
    return rows


def engine_dispatch() -> list[Row]:
    """The heterogeneous-dispatch decision itself (per-op planning cost),
    and the same op resolved by LayerSchedule lookup instead."""
    from repro.configs.base import ModelConfig
    from repro.core.engine import Engine
    from repro.core.schedule import LayerSchedule
    eng = Engine()
    x = jnp.ones((8, 4096), jnp.bfloat16)
    w = jnp.ones((4096, 4096), jnp.bfloat16)
    with eng.tracing() as tr:
        t0 = time.perf_counter()
        eng.matmul(x, w, name="bench")
        us = (time.perf_counter() - t0) * 1e6
    regime = tr[0]["regime"]
    xl = jnp.ones((8192, 4096), jnp.bfloat16)
    with eng.tracing() as tr2:
        t0 = time.perf_counter()
        eng.matmul(xl, w, name="bench")
        us_train = (time.perf_counter() - t0) * 1e6
    cfg = ModelConfig(name="bench", family="dense", n_layers=2, d_model=512,
                      n_heads=8, n_kv_heads=4, d_ff=2048, vocab_size=8192,
                      head_dim=64)
    t0 = time.perf_counter()
    LayerSchedule.compile(cfg, "decode", batch=8, max_seq=128)
    compile_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    sched = LayerSchedule.compile(cfg, "decode", batch=8, max_seq=128)
    memo_us = (time.perf_counter() - t0) * 1e6
    return [("engine/dispatch_decode", us, f"routed to {regime}"),
            ("engine/dispatch_train", us_train,
             f"routed to {tr2[0]['regime']}"),
            ("engine/schedule_compile", compile_us,
             f"{len(sched)} ops planned offline"),
            ("engine/schedule_memo_hit", memo_us, "cached object")]


def dispatch_census() -> list[Row]:
    """Per-arch regime census: how many of each architecture's matmuls the
    MPNA engine routes to each array, train vs decode (the integration of
    the paper's technique with the assigned pool)."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import SHAPES_BY_NAME
    from repro.configs.registry import all_lm_configs
    from repro.core.engine import Engine
    from repro.models import transformer as Tm
    from repro.serve import kvcache as KC
    from repro.serve.serve_step import decode_step

    eng = Engine()
    rows = []
    for arch in ("llama3-405b", "mixtral-8x7b", "mamba2-130m"):
        cfg = all_lm_configs()[arch]
        params = jax.eval_shape(
            lambda c=cfg: Tm.init_params(c, jax.random.PRNGKey(0)))
        tr_shape = SHAPES_BY_NAME["train_4k"]
        toks = jax.ShapeDtypeStruct((tr_shape.global_batch,
                                     tr_shape.seq_len), jnp.int32)
        with eng.tracing() as tr, eng.activate():
            jax.eval_shape(lambda p, t, c=cfg: Tm.loss_fn(c, p,
                                                          {"tokens": t}),
                           params, toks)
        mm = [t for t in tr if t["regime"] in ("sa_conv", "sa_fc")]
        conv = sum(t["regime"] == "sa_conv" for t in mm)
        rows.append((f"dispatch/{arch}/train_4k", 0.0,
                     f"{conv}/{len(mm)} matmuls -> sa_conv"))

        cache = jax.eval_shape(
            lambda c=cfg: KC.init_cache(c, 128, 1024, dtype=jnp.bfloat16))
        dt = jax.ShapeDtypeStruct((128, 1), jnp.int32)
        with eng.tracing() as tr2, eng.activate():
            jax.eval_shape(lambda p, ca, t, c=cfg: decode_step(c, p, ca, t,
                                                               jnp.int32(7)),
                           params, cache, dt)
        mm2 = [t for t in tr2 if t["regime"] in ("sa_conv", "sa_fc")]
        fc = sum(t["regime"] == "sa_fc" for t in mm2)
        rows.append((f"dispatch/{arch}/decode", 0.0,
                     f"{fc}/{len(mm2)} matmuls -> sa_fc"))
    return rows


ALL = [matmul_planner, conv_planner, conv_kernels, kernels_pallas,
       engine_dispatch, dispatch_census]
