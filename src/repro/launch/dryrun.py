import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           "--xla_allow_excess_precision=false")

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against the production mesh, record memory / cost / collective
analysis (EXPERIMENTS.md §Dry-run feeds on the JSON this writes).

The two lines above MUST stay the first statements in this file: jax locks
the device count at first init, and only the dry-run may see 512 host
devices (smoke tests and benches see 1).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
        --shape decode_32k --multi-pod
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import (SHAPES_BY_NAME, ModelConfig, ShapeConfig,
                                TrainConfig)
from repro.configs.registry import all_lm_configs
from repro.core import roofline
from repro.distributed import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim import adamw
from repro.serve import kvcache as KC
from repro.serve import serve_step as SS
from repro.train import train_step as TS

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")
CODE_VERSION = 6          # bump to invalidate cached dry-run JSONs


# ---------------------------------------------------------------------------
# per-cell configuration
# ---------------------------------------------------------------------------
def audio_frames_for(shape: ShapeConfig) -> int:
    return max(128, shape.seq_len // 4)


def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("pure full-attention arch: 500k decode KV is unbounded "
                "(assignment: skip, noted in DESIGN.md §6)")
    if shape.name == "long_500k" and cfg.enc_dec:
        return "enc-dec: 500k autoregressive decode outside operating regime"
    return None


def train_config_for(cfg: ModelConfig, shape: ShapeConfig,
                     mesh) -> TrainConfig:
    n = cfg.n_params()
    dp = SH.dp_size(mesh)
    if n > 100e9:
        mb, remat, mdt = 4 * dp, "block", "bfloat16"   # 4 seq/shard/microbatch
    elif n > 20e9:
        mb, remat, mdt = 2 * dp, "block", "bfloat16"
    else:
        mb, remat, mdt = 0, "block", "float32"
    if mb >= shape.global_batch:
        mb = 0
    return TrainConfig(global_batch=shape.global_batch,
                       seq_len=shape.seq_len, microbatch=mb, remat=remat,
                       moment_dtype=mdt)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for the mode's data inputs."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    s_text = S - (cfg.vision_tokens or 0)
    specs = {"tokens": jax.ShapeDtypeStruct((B, s_text), jnp.int32)}
    if cfg.vision_tokens:
        specs["vision_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.vision_tokens, cfg.frontend_dim), jnp.bfloat16)
    if cfg.enc_dec:
        specs["audio_embeds"] = jax.ShapeDtypeStruct(
            (B, audio_frames_for(shape), cfg.frontend_dim), jnp.bfloat16)
    return specs


def _sds(tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


# ---------------------------------------------------------------------------
# lowering per mode
# ---------------------------------------------------------------------------
def lower_train(cfg, shape, mesh):
    tc = train_config_for(cfg, shape, mesh)
    params_s = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    opt_s = jax.eval_shape(lambda: adamw.init(params_s, tc))
    cstate_s = jax.eval_shape(
        lambda: TS.init_train_state(cfg, tc, jax.random.PRNGKey(0))[2])
    batch_s = input_specs(cfg, shape)

    psh = SH.param_shardings(cfg, params_s, mesh)
    osh = SH.opt_shardings(cfg, opt_s, mesh)
    csh = SH.replicated(mesh, cstate_s)
    bsh = SH.batch_shardings(mesh, batch_s)

    step = TS.make_train_step(cfg, tc)
    jitted = jax.jit(step,
                     in_shardings=(psh, osh, csh, bsh),
                     out_shardings=(psh, osh, csh, None),
                     donate_argnums=(0, 1, 2))
    lowered = jitted.lower(params_s, opt_s, cstate_s, batch_s)
    tokens = shape.global_batch * shape.seq_len
    mflops = roofline.model_flops_train(cfg.n_active_params(), tokens)
    return lowered, mflops, dataclasses.asdict(tc)


def lower_prefill(cfg, shape, mesh):
    params_s = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    batch_s = input_specs(cfg, shape)
    psh = SH.param_shardings(cfg, params_s, mesh, serve=True)
    bsh = SH.batch_shardings(mesh, batch_s)

    def fn(params, batch):
        return SS.prefill_step(cfg, params, batch, shape.seq_len)

    out_s = jax.eval_shape(fn, params_s, batch_s)
    out_sh = (SH.batch_shardings(mesh, out_s[0]),
              SH.cache_shardings(cfg, mesh, out_s[1]))
    jitted = jax.jit(fn, in_shardings=(psh, bsh), out_shardings=out_sh)
    lowered = jitted.lower(params_s, batch_s)
    tokens = shape.global_batch * shape.seq_len
    mflops = roofline.model_flops_decode(cfg.n_active_params(), tokens)
    return lowered, mflops, {}


def lower_decode(cfg, shape, mesh, quant: bool = False):
    params_s = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    if quant:
        from repro.core import quant as Q
        params_s = jax.eval_shape(Q.quantize_params, params_s)
    enc_len = audio_frames_for(shape) if cfg.enc_dec else 0
    cache_s = jax.eval_shape(
        lambda: KC.init_cache(cfg, shape.global_batch, shape.seq_len,
                              enc_len=enc_len, dtype=jnp.bfloat16))
    batch_s = input_specs(cfg, shape)

    psh = SH.param_shardings(cfg, params_s, mesh, serve=True)
    cash = SH.cache_shardings(cfg, mesh, cache_s)
    bsh = SH.batch_shardings(mesh, batch_s)
    pos_s = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(params, cache, tokens, pos):
        return SS.decode_step(cfg, params, cache, tokens, pos)

    out_s = jax.eval_shape(fn, params_s, cache_s, batch_s["tokens"], pos_s)
    out_sh = (SH.batch_shardings(mesh, out_s[0]), cash)
    jitted = jax.jit(fn,
                     in_shardings=(psh, cash, bsh["tokens"],
                                   NamedSharding(mesh, P())),
                     out_shardings=out_sh, donate_argnums=(1,))
    lowered = jitted.lower(params_s, cache_s, batch_s["tokens"], pos_s)
    mflops = roofline.model_flops_decode(cfg.n_active_params(),
                                         shape.global_batch)
    return lowered, mflops, {"cache_bytes": KC.cache_bytes(cache_s)}


LOWER = {"train": lower_train, "prefill": lower_prefill,
         "decode": lower_decode}


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, multi_pod: bool,
             force: bool = False, quant: bool = False) -> dict:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    suffix = "__w8" if quant else ""
    path = os.path.join(RESULTS_DIR,
                        f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            cached = json.load(f)
        if cached.get("code_version") == CODE_VERSION:
            return cached

    cfg = all_lm_configs()[arch]
    shape = SHAPES_BY_NAME[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": mesh_name + ("(w8)" if quant else ""),
           "kind": shape.kind, "code_version": CODE_VERSION,
           "n_params": cfg.n_params(),
           "n_active_params": cfg.n_active_params()}

    reason = skip_reason(cfg, shape)
    if reason:
        rec.update(status="skipped", reason=reason)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    try:
        from repro.kernels import ref as _ref
        from repro.models import transformer as _T
        _ref.set_accum_dtype(jnp.bfloat16)   # Megatron bf16-TP payloads
        # SP residual carry: capacity lever for >100B trains (see §Perf)
        _T.SP_CARRY["on"] = cfg.n_params() > 100e9 and shape.kind == "train"
        t0 = time.time()
        with mesh, SH.activation_mesh(mesh):
            if quant:
                assert shape.kind == "decode", "w8 variant is decode-only"
                lowered, mflops, extra = lower_decode(cfg, shape, mesh,
                                                      quant=True)
            else:
                lowered, mflops, extra = LOWER[shape.kind](cfg, shape, mesh)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            mem = compiled.memory_analysis()
            terms = roofline.terms_from_compiled(compiled, chips, mflops)
            colls = roofline.collective_stats(compiled.as_text())
        dom, tdict = terms.dominant()
        rec.update(
            status="ok", lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            peak_bytes_per_chip=(mem.argument_size_in_bytes
                                 + mem.output_size_in_bytes
                                 + mem.temp_size_in_bytes
                                 - mem.alias_size_in_bytes),
            flops_per_chip=terms.flops_per_chip,
            hbm_bytes_per_chip=terms.hbm_bytes_per_chip,
            wire_bytes_per_chip=terms.wire_bytes_per_chip,
            collectives={k: v for k, v in colls.items()},
            model_flops=mflops,
            terms_s=tdict, dominant=dom,
            bound_s=terms.bound_s(),
            useful_flops_fraction=terms.useful_flops_fraction(),
            roofline_fraction=terms.roofline_fraction(),
            **extra)
    except Exception as e:                       # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def summarize(rec: dict) -> str:
    if rec["status"] == "skipped":
        return (f"{rec['arch']:26s} {rec['shape']:12s} {rec['mesh']:10s} "
                f"SKIP ({rec['reason'][:60]})")
    if rec["status"] == "error":
        return (f"{rec['arch']:26s} {rec['shape']:12s} {rec['mesh']:10s} "
                f"ERROR {rec['error'][:80]}")
    t = rec["terms_s"]
    return (f"{rec['arch']:26s} {rec['shape']:12s} {rec['mesh']:10s} "
            f"compile {rec['compile_s']:6.1f}s "
            f"mem/chip {rec['peak_bytes_per_chip']/2**30:6.2f}GiB "
            f"C {t['compute']*1e3:8.2f}ms M {t['memory']*1e3:8.2f}ms "
            f"N {t['collective']*1e3:8.2f}ms -> {rec['dominant']:10s} "
            f"roofline {rec['roofline_fraction']*100:5.1f}%")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--quant", action="store_true",
                    help="int8-weight variant (decode cells only)")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else sorted(all_lm_configs())
    shapes = [args.shape] if args.shape else list(SHAPES_BY_NAME)
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, force=args.force,
                               quant=args.quant)
                print(summarize(rec), flush=True)
                failures += rec["status"] == "error"
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
