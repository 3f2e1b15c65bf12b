"""Multi-tenant model-zoo serving demo — one engine, many compiled
models, SLO-aware dual-array wave scheduling.

Builds the three-variant zoo (AlexNet fp32, VGG-16 fp32, AlexNet int8 —
width-scaled so interpret-mode CPU execution stays seconds-scale, priced
at full paper geometry), replays one seeded mixed tenant trace under all
three scheduling policies, and prints each policy's decision log and
per-tenant SLO report.  Every request's logits are checked bitwise
against its model's own unbatched forward — the policy changes *when* a
wave dispatches, never what it computes.

    PYTHONPATH=src python examples/zoo_serve.py

CI smoke (smaller trace):

    PYTHONPATH=src python examples/zoo_serve.py --per-tenant 2
"""
import argparse

import jax.numpy as jnp
import numpy as np

from repro.models import cnn
from repro.serve.zoo import POLICIES, ModelZooServer, ZooRequest, build_zoo

RES = {"alexnet": 67, "vgg16": 32}
WIDTH = 0.125


def make_requests(per_tenant: int):
    """The mixed tagged stream: a VGG-16 batch tenant front-loading
    expensive waves, a deadline-tight int8 realtime tenant, and a
    best-effort fp32 web tenant."""
    rng = np.random.default_rng(0)
    plan = [("batch", "vgg16", "vgg16", None),
            ("rt", "alexnet-int8", "alexnet", 1.0e-3),
            ("web", "alexnet", "alexnet", 3.0e-3)]
    reqs, uid = [], 0
    for i in range(per_tenant):
        for tenant, model, net, rel_dl in plan:
            t = i * 2.0e-4 + {"batch": 0.0, "rt": 0.5e-4,
                              "web": 1.0e-4}[tenant]
            r = RES[net]
            reqs.append(ZooRequest(
                uid=uid, model=model, tenant=tenant,
                image=rng.standard_normal((r, r, 3)).astype(np.float32),
                arrival_s=t,
                deadline_s=None if rel_dl is None else t + rel_dl))
            uid += 1
    return reqs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--per-tenant", type=int, default=4,
                    help="requests per tenant (3 tenants)")
    ap.add_argument("--max-batch", type=int, default=2,
                    help="admission cap per model server")
    args = ap.parse_args(argv)

    print("== the zoo: three compiled variants, one engine ==")
    models = build_zoo(("alexnet", "vgg16", "alexnet-int8"), seed=0,
                       in_res=RES, width_mult=WIDTH,
                       max_batch=args.max_batch)
    for m in models:
        c = m.wave_cost(m.microbatch)
        print(f"  {m.name:13s} net={m.spec.net:8s} "
              f"weights={m.spec.weight_dtype:7s} micro-batch="
              f"{m.microbatch} modeled wave (b={m.microbatch}): "
              f"conv {c.conv_s*1e6:7.1f}us / fc {c.fc_s*1e6:7.1f}us")

    refs = {}
    for policy_name in ("fifo", "smf", "edf"):
        print(f"\n== policy: {policy_name} ==")
        zoo = ModelZooServer(
            build_zoo(("alexnet", "vgg16", "alexnet-int8"), seed=0,
                      in_res=RES, width_mult=WIDTH,
                      max_batch=args.max_batch),
            policy=POLICIES[policy_name]())
        reqs = make_requests(args.per_tenant)
        for r in reqs:
            zoo.submit(r)
        report = zoo.serve()
        for d in report.decisions:
            print(f"  wave {d.index}: t={d.t_s*1e6:7.1f}us {d.model:13s} "
                  f"uids={list(d.uids)} (conv {d.conv_s*1e6:.0f}us, "
                  f"fc {d.fc_s*1e6:.0f}us)")
        print("\n".join("  " + line
                        for line in report.summary().splitlines()))
        by_name = {m.name: m for m in zoo.models.values()}
        for r in report.requests:
            m = by_name[r.model]
            if r.uid not in refs:
                y = cnn.cnn_forward(m.spec.net, m.params,
                                    jnp.asarray(r.image)[None],
                                    eng=m.server.engine)
                refs[r.uid] = np.asarray(y)[0]
            assert np.array_equal(r.logits, refs[r.uid]), \
                f"uid {r.uid} logits drifted under {policy_name}"
        print(f"  parity: all {len(report.requests)} requests bitwise-"
              "equal their model's unbatched forward")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
