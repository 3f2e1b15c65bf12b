"""Fused SA-CONV -> maxpool epilogue benchmark — the machine-readable perf
trajectory for the paper's Fig. 7 pipeline.

Measures, per configuration (width-scaled AlexNet / VGG-16 CONV stacks):

* interpret-mode wall-clock of the fused dispatch (conv+pool in ONE
  pallas_call, pooled OFM out of the flush epilogue) vs. the unfused
  composition (conv pallas_call -> HBM -> standalone pool pallas_call),
  for the full CONV stack and for the conv+pool *pairs* (the layers the
  fusion actually touches — AlexNet's conv3/conv4 have no pool and run
  identical code on both paths);
* the planner-modeled HBM bytes of both schedules (what a TPU lowering
  commits to), from the same compiled ``LayerSchedule`` the engine runs.

The headline configurations run AlexNet under an accelerator-class VMEM
budget (8.625 MiB): there the pooled output block that
``ConvPlan.fuse_pool`` credits against ``vmem_bytes`` is exactly what
keeps the conv1 11x11 patch tile inside the budget, so the fused plan
contracts all 121 taps in one MXU pass while the unfused plan must
stream them tap-wise — the fused epilogue speeds up the *convolution
itself*, on top of deleting the pool pass and the OFM roundtrip.  The
benchmark records whether that flip engaged (``tap_flip``) so planner
changes that move the window are visible in the artifact.

Writes ``BENCH_conv_fused.json`` so the trajectory is diffable across PRs:

    PYTHONPATH=src python benchmarks/conv_fused.py --fast --out BENCH_conv_fused.json
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

try:                                    # package import (benchmarks.run)
    from benchmarks.timing import interleaved_medians, \
        raise_on_failed_checks, run_emit_cli
except ImportError:                     # direct script execution
    from timing import interleaved_medians, raise_on_failed_checks, \
        run_emit_cli

Row = tuple[str, float, str]

#: Accelerator-class on-chip budget for the headline configs: inside
#: its window, AlexNet conv1's fused-pool plan keeps tap fusion (the
#: 11x11 patch tile + the POOLED output block fit) while the unfused plan
#: (full 55x55 output block) must stream all 121 taps through the
#: accumulator one dot at a time — the fused epilogue's VMEM credit
#: speeds up the convolution itself (~20x on that layer in interpret
#: mode), on top of the deleted pool pass + OFM roundtrip.  Under the
#: tiled VMEM model (``dataflow.conv_vmem_bytes``) the window is
#: 9014656-9096576 B (8.60-8.68 MiB) at both widths: conv1's 96 (w=1.0)
#: and 24 (w=0.25) output channels both pad to one 128-lane tile, and its
#: patch tile is channel-independent (ci = 3 either way).
FLIP_VMEM_BUDGET = 9043968          # 8.625 MiB, both widths

#: (net, width_mult, in_res, batch, vmem_budget, reps, trials) per tier.
#: Resolutions are chosen so the pool windows tile their OFMs (the plan
#: fuses every conv+pool pair); the fast tier stays in CI-smoke territory.
CONFIGS = {
    "fast": [("alexnet", 0.25, 67, 2, None, 5, 5),
             ("vgg16", 0.125, 32, 2, None, 5, 5)],
    "full": [("alexnet", 1.0, 227, 1, FLIP_VMEM_BUDGET, 3, 9),
             ("alexnet", 0.25, 227, 1, FLIP_VMEM_BUDGET, 3, 9),
             ("alexnet", 0.25, 227, 1, None, 3, 7),
             ("vgg16", 0.25, 64, 1, None, 3, 7)],
}


def _conv_stack_fns(net: str, params, eng):
    """(fused_fn, unfused_fn) over the CONV(+pool) prefix of the network:
    identical math, the fused one dispatches each conv+pool pair as a
    single engine op, the unfused one forces conv -> HBM -> pool."""
    from repro.core.dataflow import PoolSpec
    from repro.models.cnn import NETWORKS
    spec, _ = NETWORKS[net]

    def run(x, fused: bool):
        i = ci = pi = 0
        while i < len(spec) and spec[i].kind != "fc":
            s, p = spec[i], params[i]
            if s.kind == "conv":
                ci += 1
                nxt = spec[i + 1] if i + 1 < len(spec) else None
                if nxt is not None and nxt.kind == "pool":
                    if fused:
                        x = eng.conv2d(x, p["f"], p["b"], stride=s.stride,
                                       pad=s.pad, act=s.act,
                                       pool=PoolSpec(nxt.kernel, nxt.stride),
                                       name=f"conv{ci}")
                    else:
                        x = eng.conv2d(x, p["f"], p["b"], stride=s.stride,
                                       pad=s.pad, act=s.act,
                                       name=f"conv{ci}")
                        pi += 1
                        x = eng.pool(x, window=nxt.kernel, stride=nxt.stride,
                                     name=f"pool{pi}")
                    i += 2
                    continue
                x = eng.conv2d(x, p["f"], p["b"], stride=s.stride,
                               pad=s.pad, act=s.act, name=f"conv{ci}")
            else:                                       # standalone pool
                pi += 1
                x = eng.pool(x, window=s.kernel, stride=s.stride,
                             name=f"pool{pi}")
            i += 1
        return x

    return (lambda x: run(x, True)), (lambda x: run(x, False))


def _pair_fns(net: str, params, eng, x):
    """Per conv+pool pair: (input activation, fused_fn, unfused_fn).  The
    input to each pair is precomputed by running the stack prefix once, so
    the timed region holds exactly the layers the fusion touches (convs
    without a trailing pool run identical code on both paths and only
    dilute the stack-level A/B)."""
    from repro.core.dataflow import PoolSpec
    from repro.models.cnn import NETWORKS
    spec, _ = NETWORKS[net]
    pairs = []
    i = ci = 0
    while i < len(spec) and spec[i].kind != "fc":
        s, p = spec[i], params[i]
        if s.kind == "conv":
            ci += 1
            nxt = spec[i + 1] if i + 1 < len(spec) else None
            if nxt is not None and nxt.kind == "pool":
                def fused(v, p=p, s=s, nxt=nxt, ci=ci):
                    return eng.conv2d(v, p["f"], p["b"], stride=s.stride,
                                      pad=s.pad, act=s.act,
                                      pool=PoolSpec(nxt.kernel, nxt.stride),
                                      name=f"conv{ci}")

                def unfused(v, p=p, s=s, nxt=nxt, ci=ci):
                    y = eng.conv2d(v, p["f"], p["b"], stride=s.stride,
                                   pad=s.pad, act=s.act, name=f"conv{ci}")
                    return eng.pool(y, window=nxt.kernel, stride=nxt.stride,
                                    name=f"conv{ci}.pool")

                pairs.append((x, fused, unfused))
                x = fused(x)
                i += 2
                continue
            x = eng.conv2d(x, p["f"], p["b"], stride=s.stride, pad=s.pad,
                           act=s.act, name=f"conv{ci}")
        else:
            x = eng.pool(x, window=s.kernel, stride=s.stride)
        i += 1
    return pairs


def _ab_wall(fused_fn, unfused_fn, x, *, reps: int, trials: int) -> dict:
    """Interleaved A/B medians (benchmarks/timing.py — the shared
    estimator): robust to the noisy-neighbour drift a CPU container sees
    at millisecond scales."""
    m = interleaved_medians({"fused": lambda: fused_fn(x),
                             "unfused": lambda: unfused_fn(x)},
                            reps=reps, trials=trials)
    return {"fused": m["fused"], "unfused": m["unfused"],
            "speedup": m["unfused"] / m["fused"]}


def bench_net(net: str, width_mult: float, in_res: int, batch: int = 1,
              vmem_budget: int | None = None, *,
              reps: int = 3, trials: int = 7) -> dict:
    import numpy as np

    from repro.core.engine import DispatchPolicy, Engine
    from repro.core.roofline import fused_pool_traffic_from_schedule
    from repro.core.schedule import LayerSchedule
    from repro.models import cnn

    params = cnn.init_cnn(net, jax.random.PRNGKey(0), in_res=in_res,
                          width_mult=width_mult)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, in_res, in_res, 3),
                          jnp.float32)
    policy = DispatchPolicy(vmem_budget=vmem_budget)
    eng = Engine(backend="pallas", policy=policy)

    fused_fn, unfused_fn = _conv_stack_fns(net, params, eng)
    # numerics: a tap-mode flip changes accumulation order (one fused dot
    # vs a tap-wise sum), so parity is allclose; the exact-match guarantee
    # (same kernel mode) is covered by tests/test_fused_pool.py.  The
    # check is recorded (and fails the process via emit()) instead of
    # silently publishing an artifact whose two paths disagree.
    yf, yu = np.asarray(fused_fn(x)), np.asarray(unfused_fn(x))
    parity = {"name": f"parity/{net}_w{width_mult}_r{in_res}"
                      f"_vmem{vmem_budget}",
              "passed": bool(np.allclose(yf, yu, rtol=1e-3, atol=1e-3)),
              "detail": f"max|fused-unfused|={float(np.max(np.abs(yf-yu)))}"}
    wall_stack = _ab_wall(fused_fn, unfused_fn, x, reps=reps, trials=trials)
    pairs = _pair_fns(net, params, eng, x)
    pf, pu = 0.0, 0.0
    for xin, f_fn, u_fn in pairs:
        w = _ab_wall(f_fn, u_fn, xin, reps=reps, trials=trials)
        pf += w["fused"]
        pu += w["unfused"]
    wall_pairs = {"fused": pf, "unfused": pu, "speedup": pu / pf}

    # planner-modeled HBM bytes of both schedules (width-scaled geometry
    # comes from the compiled schedule, the single source of truth)
    sched = LayerSchedule.compile_cnn(net, batch=batch, in_res=in_res,
                                      width_mult=width_mult, policy=policy)
    per_layer = fused_pool_traffic_from_schedule(sched)
    layers = [{"layer": name, **{k: int(v) for k, v in rep.items()}}
              for name, rep in sorted(per_layer.items())]
    hbm_fused = sum(r["fused_bytes"] for r in layers)
    hbm_unfused = sum(r["unfused_bytes"] for r in layers)
    plans = {k.name: p for k, p in sched.conv_entries.items()}
    n_fused = sum(p.fuse_pool for p in plans.values())
    # did the pooled output block keep tap fusion alive where the unfused
    # plan streams?  (the headline mechanism; see module docstring)
    tap_flip = False
    for key, plan in sched.conv_entries.items():
        if not plan.fuse_pool:
            continue
        uplan = policy.plan_conv(key.batch, key.h, key.w, key.ci, key.p,
                                 key.q, key.co, key.stride, act_bytes=4,
                                 weight_bytes=4, regime=plan.regime)
        if plan.fuse_taps and not uplan.fuse_taps:
            tap_flip = True
    return {
        "net": net, "width_mult": width_mult, "in_res": in_res,
        "batch": batch, "reps": reps, "trials": trials,
        "vmem_budget": vmem_budget,
        "fused_pairs": int(n_fused),
        "tap_flip": tap_flip,
        "checks": [parity],
        "wall_s": {"conv_stack": wall_stack, "conv_pool_pairs": wall_pairs},
        "planner_hbm_bytes": {"fused": int(hbm_fused),
                              "unfused": int(hbm_unfused),
                              "saving": int(hbm_unfused - hbm_fused)},
        "layers": layers,
    }


def emit(out_path: str = "BENCH_conv_fused.json", *,
         tier: str = "fast") -> list[Row]:
    """Run the benchmark, write the JSON artifact, return CSV rows for
    benchmarks/run.py."""
    results = {"bench": "conv_fused", "tier": tier,
               "backend": "pallas-interpret-cpu", "nets": []}
    rows: list[Row] = []
    for net, wm, res, batch, budget, reps, trials in CONFIGS[tier]:
        r = bench_net(net, wm, res, batch, budget, reps=reps, trials=trials)
        results["nets"].append(r)
        wp = r["wall_s"]["conv_pool_pairs"]
        ws = r["wall_s"]["conv_stack"]
        hb = r["planner_hbm_bytes"]
        tag = f"{net}_w{wm}_r{res}" + \
            (f"_vmem{budget // 2**20}M" if budget else "")
        rows.append((
            f"conv_fused/{tag}", wp["fused"] * 1e6,
            f"pairs {wp['speedup']:.2f}x / stack {ws['speedup']:.2f}x vs "
            f"unfused; planner HBM {hb['fused'] / 2**20:.1f}MiB vs "
            f"{hb['unfused'] / 2**20:.1f}MiB (-{hb['saving'] / 2**20:.1f}MiB,"
            f" {r['fused_pairs']} pairs fused"
            f"{', tap-flip' if r['tap_flip'] else ''})"))
    alex = [r for r in results["nets"] if r["net"] == "alexnet"]
    results["headline"] = {
        "alexnet_conv_pool_pairs_speedup": max(
            r["wall_s"]["conv_pool_pairs"]["speedup"] for r in alex),
        "hbm_saving_bytes": sum(
            r["planner_hbm_bytes"]["saving"] for r in results["nets"]),
    }
    results["checks"] = [c for r in results["nets"] for c in r["checks"]]
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(results, fh, indent=2)
    rows.append(("conv_fused/json", 0.0,
                 f"wrote {out_path} (headline alexnet pairs "
                 f"{results['headline']['alexnet_conv_pool_pairs_speedup']:.2f}x)"))
    raise_on_failed_checks(results["checks"])
    return rows


def bench_rows() -> list[Row]:
    """run.py group entry: fast tier, writes BENCH_conv_fused.json."""
    return emit("BENCH_conv_fused.json", tier="fast")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_conv_fused.json")
    tier = ap.add_mutually_exclusive_group()
    tier.add_argument("--fast", dest="tier", action="store_const",
                      const="fast", default="fast",
                      help="CI smoke: width-scaled, small res (seconds)")
    tier.add_argument("--full", dest="tier", action="store_const",
                      const="full",
                      help="nightly: full-res stacks incl. the VMEM-budget "
                           "tap-flip headline config")
    args = ap.parse_args()
    run_emit_cli(emit, args.out, args.tier)


if __name__ == "__main__":
    main()
