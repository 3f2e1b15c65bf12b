"""AlexNet inference through the full MPNA operator set — the paper's own
workload running on the SA-CONV / SA-FC / pooling&activation kernels
(interpret mode on CPU), plus the analytic cycle/energy report
(Figs. 1, 12; Tables I-III).

The forward runs under an explicit :class:`~repro.core.engine.Engine`
carrying a compiled :meth:`LayerSchedule.compile_cnn` schedule — the
paper's offline per-layer table: every CONV resolves its implicit-GEMM
:class:`~repro.core.dataflow.ConvPlan` and every FC its batch-amortized
:class:`~repro.core.dataflow.FCPlan` by lookup (``hit``), not by
re-planning at trace time.  No im2col patch matrix is materialized.

    PYTHONPATH=src python examples/alexnet_mpna.py

CI smoke (the full-resolution forward is >280 s on a CPU runner):

    PYTHONPATH=src python examples/alexnet_mpna.py --res 67 --batch 1
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import perf_model as PM
from repro.core.engine import Engine
from repro.core.schedule import LayerSchedule
from repro.models import cnn


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--res", type=int, default=227,
                    help="input resolution of the full-width CONV-stack "
                         "section (227 = paper; 67 is the smallest AlexNet "
                         "supports and makes a seconds-scale CI smoke)")
    ap.add_argument("--batch", type=int, default=2,
                    help="batch of the reduced functional demo + serving "
                         "section")
    args = ap.parse_args(argv)

    print("== functional: AlexNet on the MPNA kernels (reduced size) ==")
    params = cnn.init_cnn("alexnet", jax.random.PRNGKey(0), in_res=67,
                          width_mult=0.125)
    x = jax.random.normal(jax.random.PRNGKey(1), (args.batch, 67, 67, 3),
                          jnp.float32)

    sched = LayerSchedule.compile_cnn("alexnet", batch=args.batch, in_res=67,
                                      width_mult=0.125)
    eng = Engine(backend="pallas").with_schedule(sched)
    with eng.tracing() as tr:
        t0 = time.perf_counter()
        y_mpna = cnn.cnn_forward("alexnet", params, x, eng=eng)
        t1 = time.perf_counter()
    y_ref = cnn.cnn_forward("alexnet", params, x, backend="xla")
    np.testing.assert_allclose(y_mpna, y_ref, rtol=2e-4, atol=2e-4)
    print(f"  SA-CONV/SA-FC/pool-act pipeline == oracle "
          f"(logits {y_mpna.shape}, {t1-t0:.1f}s incl. compile, "
          f"implicit GEMM)")
    hits = sum(r.schedule == "hit" for r in tr)
    print(f"  dispatches: {len(tr)} ops, {hits} resolved from the compiled "
          f"schedule")
    print("\n".join("    " + line for line in tr.summary().splitlines()))

    # steady-state wall time vs the legacy materialized-im2col CONV path
    def legacy_forward(pr, xv):
        from repro.kernels.conv2d import conv2d_im2col
        from repro.kernels.pool_act import maxpool_act
        spec, _ = cnn.NETWORKS["alexnet"]
        for s, p in zip(spec, pr):
            if s.kind == "conv":
                if s.pad:
                    xv = jnp.pad(xv, ((0, 0), (s.pad, s.pad),
                                      (s.pad, s.pad), (0, 0)))
                xv = conv2d_im2col(xv, p["f"], p["b"], stride=s.stride,
                                   act=s.act)
            elif s.kind == "pool":
                xv = maxpool_act(xv, window=s.kernel, stride=s.stride,
                                 act="none")
            else:
                xv = eng.matmul(xv.reshape(xv.shape[0], -1), p["w"],
                                p["b"], act=s.act)
        return xv

    jax.block_until_ready(legacy_forward(params, x))
    t0 = time.perf_counter()
    jax.block_until_ready(legacy_forward(params, x))
    t_old = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(cnn.cnn_forward("alexnet", params, x, eng=eng))
    t_new = time.perf_counter() - t0
    print(f"  forward wall time: implicit GEMM {t_new*1e3:.1f} ms vs "
          f"im2col path {t_old*1e3:.1f} ms ({t_old/t_new:.1f}x)")

    print(f"\n== full-width CONV stack ({args.res}x{args.res}, the layers "
          "this kernel owns) ==")
    full = cnn.init_cnn("alexnet", jax.random.PRNGKey(0), in_res=args.res)
    xf = jax.random.normal(jax.random.PRNGKey(2),
                           (1, args.res, args.res, 3), jnp.float32)
    spec, _ = cnn.NETWORKS["alexnet"]

    def conv_stack(fn_conv, xv):
        from repro.kernels.pool_act import maxpool_act
        for s, p in zip(spec, full):
            if s.kind == "conv":
                xv = fn_conv(xv, p, s)
            elif s.kind == "pool":
                xv = maxpool_act(xv, window=s.kernel, stride=s.stride,
                                 act="none")
            else:
                break
        return xv

    def implicit_conv(xv, p, s):
        return eng.conv2d(xv, p["f"], p["b"], stride=s.stride, pad=s.pad,
                          act=s.act)

    def im2col_conv(xv, p, s):
        from repro.kernels.conv2d import conv2d_im2col
        if s.pad:
            xv = jnp.pad(xv, ((0, 0), (s.pad, s.pad), (s.pad, s.pad),
                              (0, 0)))
        return conv2d_im2col(xv, p["f"], p["b"], stride=s.stride, act=s.act)

    for label, fn in (("implicit GEMM", implicit_conv),
                      ("im2col path  ", im2col_conv)):
        jax.block_until_ready(conv_stack(fn, xf))          # compile
        t0 = time.perf_counter()
        jax.block_until_ready(conv_stack(fn, xf))
        print(f"  {label}: {(time.perf_counter()-t0)*1e3:7.1f} ms "
              f"(conv1-conv5 + pools)")

    print("\n== offline schedule: per-layer plans (paper Sec. V table) ==")
    print("\n".join("  " + line for line in sched.table().splitlines()))

    print("\n== implicit-GEMM CONV traffic vs the deleted im2col path ==")
    print("   (conv+pool pairs run the fused flush epilogue: the full OFM")
    print("    never crosses HBM — 'unfused' is the conv->HBM->pool bytes)")
    for row in PM.pallas_conv_traffic("alexnet", batch=1):
        p = row.plan
        pooltag = f" pool{p.pool_window}s{p.pool_stride} fused, unfused " \
            f"path {row.unfused_bytes/2**20:.1f} MiB" if p.fuse_pool else ""
        print(f"  {row.layer}: planned {p.hbm_bytes/2**20:6.1f} MiB "
              f"(compulsory {row.compulsory_bytes/2**20:6.1f}, "
              f"im2col path moved {row.im2col_bytes/2**20:6.1f}) "
              f"case {p.case} tile (bi={p.bi}, bj={p.bj}){pooltag}")

    print("\n== batch-amortized SA-FC: the classifier head's weight stream "
          "==")
    print("   (per-sample FC weight reuse = 1 — the only traffic lever is")
    print("    the batch: each weight byte streams once per resident batch")
    print("    tile, so weights-bytes/sample falls ~B-fold)")
    for b in (1, 16, 64, 256):
        rows = PM.pallas_fc_traffic("alexnet", batch=b)
        stack = sum(r.weight_bytes_per_sample for r in rows)
        tags = " ".join(f"{r.layer}:bb={r.plan.bb}x{r.plan.weight_passes}p"
                        for r in rows)
        print(f"  b={b:4d}: {stack / 2**20:7.2f} MiB weights/sample  {tags}")
    flips = {r.layer: r.plan.flip_batch
             for r in PM.pallas_fc_traffic("alexnet", batch=1)}
    print(f"  planner-pinned memory-bound flip batches: {flips}")

    print("\n== micro-batch CNN serving (the batching that buys the "
          "amortization) ==")
    from repro.serve.cnn_server import CNNRequest, CNNServer
    srv = CNNServer("alexnet", params, in_res=67, width_mult=0.125,
                    max_batch=8)
    rng = np.random.default_rng(0)
    n_req = max(3, args.batch)
    for i in range(n_req):
        srv.submit(CNNRequest(uid=i, image=rng.standard_normal(
            (67, 67, 3)).astype(np.float32)))
    done = srv.run()
    wave = srv.waves[0]
    print(f"  {n_req} single-image requests -> {len(srv.waves)} dispatch "
          f"wave(s), micro-batch {srv.microbatch} "
          f"(planner's resident batch tile)")
    print(f"  wave 0: batch {wave.batch}, {wave.schedule_hits} schedule "
          f"hits, FC layers carry FCPlans: "
          f"{[(r.name, r.fc_plan.bb) for r in wave.fc_records]}")
    one = cnn.cnn_forward("alexnet", params,
                          jnp.asarray(done[0].image)[None], eng=eng)
    print(f"  bitwise-equal to the unbatched forward: "
          f"{bool(np.array_equal(np.asarray(one)[0], done[0].logits))}")

    print("\n== dual-array pipelined serving (SA-CONV || SA-FC across "
          "waves) ==")
    srv_p = CNNServer("alexnet", params, in_res=67, width_mult=0.125,
                      max_batch=2, pipeline=True)
    srv_s = CNNServer("alexnet", params, in_res=67, width_mult=0.125,
                      max_batch=2, pipeline=False)
    for i in range(4):
        img = rng.standard_normal((67, 67, 3)).astype(np.float32)
        srv_p.submit(CNNRequest(uid=i, image=img.copy()))
        srv_s.submit(CNNRequest(uid=i, image=img))
    done_p, done_s = srv_p.run(), srv_s.run()
    same = all(np.array_equal(a.logits, b.logits)
               for a, b in zip(done_p, done_s))
    w0 = srv_p.waves[0]
    print(f"  {len(done_p)} requests in {len(srv_p.waves)} overlapped "
          f"waves; wave 0 trace: {len(w0.conv_trace)} conv-stage + "
          f"{len(w0.fc_trace)} fc-stage records (stage/wave tagged)")
    print(f"  pipelined logits bitwise-equal sequential path: {same}")
    for net in ("alexnet", "vgg16"):
        m = PM.pipeline_makespan(net, batch=8, waves=8)
        cs_us, fs_us = (v * 1e6 for v in PM.pipeline_stage_seconds(net, 8))
        print(f"  {net:8s} b=8 waves=8: modeled makespan ratio "
              f"{m.makespan_ratio:.3f}x (ASIC), stage roofline "
              f"conv {cs_us:.0f}us / fc {fs_us:.0f}us, FC->CONV "
              f"bottleneck crossover b="
              f"{PM.tpu_pipeline_crossover_batch(net)}")

    print("\n== analytic: the paper's headline numbers ==")
    print(f"  Fig 12a  SA-FC speedup on FC : "
          f"{PM.fig12a_safc_speedup():.2f}x   (paper 8.1x)")
    for n, v in PM.fig12b_mpna_speedup().items():
        print(f"  Fig 12b  MPNA vs conv {n}x{n}   : {v:.2f}x   "
              f"(paper band 1.4-7.2x)")
    print(f"  Fig 12c  DRAM access saving  : "
          f"{PM.fig12c_access_reduction()*100:.1f}%  (paper 53%)")
    print(f"  Fig 12e  energy saving       : "
          f"{PM.fig12e_energy_saving()*100:.1f}%  (paper 51%)")
    t3 = PM.table3_throughput()
    print(f"  Table III GOPS               : {t3['gops']:.1f} "
          f"(paper 35.8; ours omits DMA/control stalls)")
    print(f"  dataflow cases (AlexNet)     : "
          f"{PM.mpna_traffic('alexnet').case_per_layer}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
