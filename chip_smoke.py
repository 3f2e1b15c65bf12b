"""Serve full-width AlexNet (fp32 and int8) through the model zoo on one TPU.

The quickest proof that the system runs on the chip.  It builds the zoo
with ``build_zoo(("alexnet", "alexnet-int8"), width_mult=1.0)`` at the
native 227x227 resolution, submits per model one wave at the planner's
micro-batch (``CNNServer.preferred_microbatch``) plus a smaller tail wave,
serves them with ``ModelZooServer.serve()`` (compiled Pallas kernels), and
checks every request:

* it ended ``served`` — none shed or quarantined;
* its logits are within ``TOL`` (max abs error over the max abs logit) of
  the XLA oracle, ``Engine(backend="xla")`` with matmuls at
  ``Precision.HIGHEST``;
* a control, the same XLA forward at the compiler's default matmul
  precision (one bf16 pass), must miss ``TOL``: a tolerance that passes a
  bf16 computation cannot tell whether the fp32 models serve at fp32;
* whether they are bitwise equal to the unbatched pallas forward (the
  contract the CPU tests pin) is reported, not required.

``--four-chips`` runs only the fleet phase instead: four replicas, one per
device, a burst wider than one micro-batch so the fleet cuts a ``data=4``
cooperative wave (kernels under ``shard_map``), checked against the
one-device forward.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # four chips

Exits non-zero, printing no result, when no TPU is attached, when the
device kind has no planner constants, or when any check fails.  The last
line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: max |served - oracle| / max |oracle| per request.  On a TPU v5e the
#: fp32-precision kernels read 8.6e-07 (alexnet) and 6.6e-07 (int8), and
#: the default-precision control 8.3e-03 and 6.4e-03: the tolerance sits
#: about 10x above the first and far below the second.
TOL = 1e-5
MODELS = ("alexnet", "alexnet-int8")
TAIL = 3                       # rows of the smaller tail wave, per model


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _device():
    import jax

    from repro.core.accelerator import chip_for

    devs = jax.devices()
    d = devs[0]
    print(f"platform {d.platform}  device_kind {d.device_kind}  "
          f"count {len(devs)}", flush=True)
    if d.platform != "tpu":
        _fail(f"no TPU attached (platform {d.platform!r})")
    try:
        chip_for(d.device_kind)            # the planner's constants are v5e's
    except ValueError as e:
        _fail(str(e))
    return devs


def _images(seed: int, n: int, res: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, res, res, 3)).astype(np.float32)


def _xla_forward(zm, images, precision: str):
    """The XLA forward of ``zm`` with its matmuls at ``precision``
    (``"highest"``: the oracle; ``"default"``: the bf16 control)."""
    import jax
    import numpy as np

    from repro.core.engine import Engine
    from repro.models import cnn

    with jax.default_matmul_precision(precision):
        out = cnn.cnn_forward(zm.spec.net, zm.params, images,
                              eng=Engine(backend="xla"))
        return np.asarray(out)


def _rel_err(got, want) -> float:
    import numpy as np

    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def _timed_waves(zoo, walls: list) -> None:
    """Record the wall time of every wave each model's executor runs:
    ``CNNServer.step_wave`` returns after the logits are on the host, so
    the device work is done when the clock stops."""
    for zm in zoo.models.values():
        srv = zm.server

        def timed(step=srv.step_wave, name=zm.name):
            t = time.perf_counter()
            done = step()
            walls.append((name, len(done), time.perf_counter() - t))
            return done

        srv.step_wave = timed


def _serve_once(zoo, images, uid0: int, walls: list):
    from repro.serve.zoo import ZooRequest

    reqs = []
    for name, imgs in images.items():
        for i, img in enumerate(imgs):
            reqs.append(ZooRequest(uid=uid0 + len(reqs), model=name,
                                   image=img, tenant=name))
            zoo.submit(reqs[-1])
    del walls[:]
    t = time.perf_counter()
    rep = zoo.serve()
    return reqs, rep, time.perf_counter() - t


def one_chip() -> None:
    import numpy as np

    from repro.core.engine import Engine
    from repro.models import cnn
    from repro.serve.zoo import ModelZooServer, build_zoo

    t = time.perf_counter()
    zoo = ModelZooServer(build_zoo(MODELS, width_mult=1.0))
    print(f"setup_s {time.perf_counter() - t:.3f}  (seeded params, "
          "quantization, stage schedules)", flush=True)
    images = {}
    for k, (name, zm) in enumerate(zoo.models.items()):
        mb = zm.server.preferred_microbatch
        images[name] = _images(k, mb + TAIL, zm.server.in_res)
        print(f"model {name}: micro-batch {mb}, waves of {mb} and {TAIL}",
              flush=True)
    walls: list = []
    _timed_waves(zoo, walls)
    # the first drain compiles every kernel shape; the second is warm
    reqs, rep, cold = _serve_once(zoo, images, 0, walls)
    reqs, rep, warm = _serve_once(zoo, images, len(reqs), walls)
    print(f"serve_cold_s {cold:.3f}  serve_warm_s {warm:.3f}  "
          f"compile_s {cold - warm:.3f}  (cold minus warm)", flush=True)
    for name, rows, wall in walls:
        print(f"wave {name} rows {rows} wall_s {wall:.4f}", flush=True)
    bad = [r for r in rep.requests if r.status != "served"]
    if bad or len(rep.served) != len(reqs):
        _fail(f"{len(bad)} of {len(reqs)} requests not served: "
              + "; ".join(f"{r.uid} {r.status} {r.error}" for r in bad[:4]))
    for name, zm in zoo.models.items():
        mine = [r for r in reqs if r.model == name]
        got = np.stack([r.logits for r in mine])
        if got.shape != (len(mine), 1000) or not np.isfinite(got).all():
            _fail(f"{name}: logits {got.shape}, finite "
                  f"{bool(np.isfinite(got).all())}")
        want = _xla_forward(zm, images[name], "highest")
        err = max(_rel_err(g, w) for g, w in zip(got, want))
        control = max(_rel_err(g, w) for g, w in zip(
            _xla_forward(zm, images[name], "default"), want))
        eng = Engine(backend="pallas", policy=zm.server.engine.policy)
        bitwise = all(np.array_equal(
            r.logits, np.asarray(cnn.cnn_forward(
                zm.spec.net, zm.params, img[None], eng=eng))[0])
            for r, img in zip(mine, images[name]))
        print(f"model {name}: max_rel_err {err:.3e} (tol {TOL:g})  "
              f"bf16_control_err {control:.3e}  "
              f"bitwise_vs_unbatched {bitwise}", flush=True)
        if not err <= TOL:
            _fail(f"{name}: max relative error {err:.3e} > {TOL:g}")
        if control <= TOL:
            _fail(f"{name}: the default-precision control ({control:.3e}) "
                  f"passes the tolerance {TOL:g}")


def four_chips(devs) -> None:
    import numpy as np

    from repro.core.engine import Engine
    from repro.models import cnn
    from repro.serve.fleet import FleetServer
    from repro.serve.zoo import FIFOPolicy, ZooRequest, build_zoo

    if len(devs) != 4:
        _fail(f"--four-chips needs 4 devices, found {len(devs)}")
    t = time.perf_counter()
    models = build_zoo(("alexnet",), width_mult=1.0)
    fleet = FleetServer(models, n_replicas=4, policy=FIFOPolicy(),
                        shard_waves=True)
    zm = models[0]
    mb = zm.microbatch
    n = 2 * mb + TAIL                      # wider than one micro-batch
    print(f"setup_s {time.perf_counter() - t:.3f}  replicas 4  "
          f"micro-batch {mb}  burst {n}", flush=True)
    imgs = _images(7, n, zm.server.in_res)
    reqs = [ZooRequest(uid=i, model=zm.name, image=imgs[i], tenant="t")
            for i in range(n)]
    for r in reqs:
        fleet.submit(r)
    t = time.perf_counter()
    rep = fleet.serve()
    print(f"serve_s {time.perf_counter() - t:.3f} (cold)", flush=True)
    coop = [d for d in rep.decisions if d.sharded]
    for d in rep.decisions:
        print(f"wave replica {d.replica} rows {d.batch} shards "
              f"{','.join(d.shards) or '-'}", flush=True)
    if not any(len(d.shards) == 4 for d in coop):
        _fail("no data=4 cooperative wave was cut")
    bad = [r for r in rep.requests if r.status != "served"]
    if bad:
        _fail(f"{len(bad)} of {n} requests not served: "
              + "; ".join(f"{r.uid} {r.status} {r.error}" for r in bad[:4]))
    eng = Engine(backend="pallas", policy=zm.server.engine.policy)
    ref = np.asarray(cnn.cnn_forward(zm.spec.net, zm.params, imgs, eng=eng))
    got = np.stack([r.logits for r in reqs])
    err = max(_rel_err(g, w) for g, w in zip(got, ref))
    coop_uids = {u for d in coop for u in d.uids}
    print(f"sharded rows {len(coop_uids)}  max_rel_err_vs_one_device "
          f"{err:.3e} (tol {TOL:g})  bitwise {np.array_equal(got, ref)}",
          flush=True)
    if not err <= TOL:
        _fail(f"fleet logits differ from the one-device forward: {err:.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica fleet phase")
    args = ap.parse_args()
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        _fail(f"the repro package is not beside this script ({e})")
    print(f"compile_cache {enable_compile_cache()}", flush=True)
    devs = _device()
    if args.four_chips:
        four_chips(devs)
    else:
        one_chip()
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
