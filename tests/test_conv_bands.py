"""Row-band spatial tiling of the SA-CONV kernel.

A conv whose whole-map slab overflows the VMEM budget runs in bands of
output rows, each band's input window carrying the ``p' - 1`` halo rows
in phase space.  Banded plans are forced here by small budgets and
compared, in the interpreter, with the XLA oracle and bitwise with the
whole-map plan of the same layer.  The planner must keep every plan that
fits whole exactly as it was: AlexNet's and VGG-16's conv2-13 plans at
the zoo's wave of 8 are pinned field for field.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.passes import check_coverage
from repro.analysis.verify import context_for
from repro.core.dataflow import ConvPlan, PlanError, PoolSpec, plan_conv
from repro.core.engine import DispatchPolicy, Engine
from repro.core.schedule import ConvOpKey, LayerSchedule
from repro.kernels import ref
from repro.kernels.sa_conv_implicit import sa_conv_implicit
from repro.models import cnn


def _rand(key, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32) * scale


def _int8(f):
    amax = jnp.max(jnp.abs(f), axis=(0, 1, 2))
    scale = jnp.maximum(amax, 1e-8) / 127.0
    return jnp.clip(jnp.round(f / scale), -127, 127).astype(jnp.int8), scale


#: (input edge, ci, co, kernel, stride, pool, int8 filters, band forced)
CASES = {
    "s1-pool": (18, 3, 16, 3, 1, PoolSpec(2, 2), False, None),
    "s1-ragged": (19, 4, 8, 3, 1, None, False, None),
    "s2": (21, 5, 8, 5, 2, None, False, 3),
    "s2-ragged": (21, 5, 8, 5, 2, None, False, 4),
    "s1-pool-ragged": (22, 3, 16, 3, 1, PoolSpec(2, 2), False, 6),
    "s1-int8": (19, 4, 8, 3, 1, None, True, None),
    "s1-pool-int8": (18, 3, 16, 3, 1, PoolSpec(2, 2), True, None),
    "s2-int8-ragged": (21, 5, 8, 5, 2, None, True, 4),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_banded_conv_matches_oracle_and_whole_map_plan(case):
    h, ci, co, k, stride, pool, int8, forced = CASES[case]
    x = _rand(0, (2, h, h, ci))
    f = _rand(1, (k, k, ci, co), 0.2)
    b = _rand(2, (co,))
    fq, scale = _int8(f) if int8 else (f, None)
    kw = dict(stride=stride, bytes_in=4, bytes_w=1 if int8 else 4,
              pool=pool, act="relu")
    whole = plan_conv(2, h, h, ci, k, k, co, **kw)
    assert whole.bands == 1 and whole.band == 0
    oh = (h - k) // stride + 1
    if forced is None:
        # a byte short of each whole-map working set in turn, until the
        # planner bands
        banded = whole
        while banded.bands == 1:
            banded = plan_conv(2, h, h, ci, k, k, co, **kw,
                               vmem_budget=banded.vmem_bytes - 1)
    else:
        banded = dataclasses.replace(whole, band=forced,
                                     bands=-(-oh // forced))
    assert banded.bands == -(-oh // banded.band) > 1
    assert banded.fuse_pool == (pool is not None)
    if case.endswith("ragged"):
        assert oh % banded.band
    run = lambda plan: sa_conv_implicit(x, fq, b, stride=stride, act="relu",
                                        plan=plan, w_scale=scale)
    got = run(banded)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(run(whole)))
    ff = f if scale is None else fq.astype(jnp.float32) * scale
    want = ref.apply_act(ref.conv2d(x, ff, stride=stride) + b, "relu")
    if pool is not None:
        want = ref.maxpool2d(want, window=pool.window, stride=pool.stride)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_banded_plan_declines_a_pool_that_straddles_bands():
    """A 3x3/2 pool's windows overlap, so they would cross band edges:
    a plan that must band runs the conv unfused (the engine then pools
    on its own), where the whole-map plan fused it."""
    kw = dict(bytes_in=4, bytes_w=4, pool=PoolSpec(3, 2), act="relu")
    whole = plan_conv(1, 21, 21, 8, 3, 3, 32, **kw)
    assert whole.fuse_pool
    banded = plan_conv(1, 21, 21, 8, 3, 3, 32, **kw,
                       vmem_budget=whole.vmem_bytes - 1)
    assert banded.bands > 1 and not banded.fuse_pool
    x, f = _rand(0, (1, 21, 21, 8)), _rand(1, (3, 3, 8, 32), 0.2)
    eng = Engine(backend="pallas",
                 policy=DispatchPolicy(vmem_budget=whole.vmem_bytes - 1))
    with eng.tracing() as tr:
        got = eng.conv2d(x, f, act="relu", pool=PoolSpec(3, 2), name="c")
    assert [r.name for r in tr] == ["c", "c.pool"]
    want = ref.maxpool2d(ref.apply_act(ref.conv2d(x, f), "relu"),
                         window=3, stride=2)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_budget_below_a_one_row_band_is_refused():
    with pytest.raises(PlanError, match="minimum SA-CONV input band"):
        plan_conv(1, 21, 21, 64, 3, 3, 128, bytes_in=4, bytes_w=4,
                  vmem_budget=64 * 1024)


def _vgg_conv1(batch=8):
    return plan_conv(batch, 226, 226, 3, 3, 3, 64, bytes_in=4, bytes_w=4,
                     act="relu")


def test_vgg16_conv1_bands_within_the_chip_budget():
    """VGG-16's stem at 224x224, batch 8, fp32: its 3 channels fill a
    128-lane slab, so no whole-map tiling fits 96 MiB; two bands of 112
    rows do."""
    plan = _vgg_conv1()
    assert (plan.band, plan.bands) == (112, 2)
    assert plan.vmem_bytes <= 96 * 1024 ** 2
    assert (plan.bi, plan.bj, plan.fuse_taps, plan.fuse_pool) == (
        3, 64, True, False)
    with pytest.raises(PlanError):
        plan_conv(8, 226, 226, 3, 3, 3, 64, bytes_in=4, bytes_w=4,
                  act="relu", vmem_budget=1 << 20)


def _fields(plan: ConvPlan) -> tuple:
    return (plan.case, plan.regime, plan.bi, plan.bj, plan.fuse_taps,
            plan.hbm_bytes, plan.flops, plan.vmem_bytes, plan.m, plan.n,
            plan.k, plan.fuse_pool, plan.pool_window, plan.pool_stride)


#: each conv's plan at the zoo's wave of 8, fp32 activations, before row
#: bands existed: (case, regime, bi, bj, fuse_taps, hbm_bytes, flops,
#: vmem_bytes, m, n, k, fuse_pool, pool_window, pool_stride)
PINNED = {
    ("alexnet", 4): {
    "conv1": (1, 'sa_fc', 3, 96, True, 7325664, 1686643200, 6828032, 24200, 96, 363, True, 3, 2),
    "conv2": (1, 'sa_conv', 96, 256, True, 6794240, 7166361600, 7651328, 5832, 256, 2400, True, 3, 2),
    "conv3": (1, 'sa_conv', 256, 384, True, 7458816, 2392326144, 8773632, 1352, 384, 2304, False, 0, 0),
    "conv4": (1, 'sa_conv', 384, 384, True, 10149888, 3588489216, 12632064, 1352, 384, 3456, False, 0, 0),
    "conv5": (1, 'sa_conv', 384, 256, True, 6598656, 2392326144, 8413184, 1352, 256, 3456, True, 3, 2),
    },
    ("alexnet", 1): {
    "conv1": (1, 'sa_fc', 3, 96, True, 7221120, 1686643200, 6754304, 24200, 96, 363, True, 3, 2),
    "conv2": (1, 'sa_conv', 96, 256, True, 4951040, 7166361600, 6422528, 5832, 256, 2400, True, 3, 2),
    "conv3": (1, 'sa_conv', 256, 384, True, 4804608, 2392326144, 7004160, 1352, 384, 2304, False, 0, 0),
    "conv4": (1, 'sa_conv', 384, 384, True, 6168576, 3588489216, 9977856, 1352, 384, 3456, False, 0, 0),
    "conv5": (1, 'sa_conv', 384, 256, True, 3944448, 2392326144, 6643712, 1352, 256, 3456, True, 3, 2),
    },
    ("vgg16", 4): {
    "conv2": (1, 'sa_fc', 64, 64, False, 130441216, 29595009024, 93175808, 401408, 64, 576, True, 2, 2),
    "conv3": (1, 'sa_fc', 64, 128, False, 78290944, 14797504512, 34054144, 100352, 128, 576, False, 0, 0),
    "conv4": (1, 'sa_conv', 128, 128, False, 66666496, 29595009024, 25010176, 100352, 128, 1152, True, 2, 2),
    "conv5": (1, 'sa_conv', 128, 256, True, 40648704, 14797504512, 16199680, 25088, 256, 1152, False, 0, 0),
    "conv6": (1, 'sa_conv', 256, 256, False, 55607296, 29595009024, 22159360, 25088, 256, 2304, False, 0, 0),
    "conv7": (1, 'sa_conv', 256, 256, False, 36339712, 29595009024, 17571840, 25088, 256, 2304, True, 2, 2),
    "conv8": (1, 'sa_conv', 256, 512, True, 24936448, 14797504512, 17399808, 6272, 512, 2304, False, 0, 0),
    "conv9": (1, 'sa_conv', 512, 512, True, 37027840, 29595009024, 29097984, 6272, 512, 4608, False, 0, 0),
    "conv10": (1, 'sa_conv', 512, 512, True, 27394048, 29595009024, 26345472, 6272, 512, 4608, True, 2, 2),
    "conv11": (1, 'sa_conv', 512, 512, True, 16842752, 7398752256, 21725184, 1568, 512, 4608, False, 0, 0),
    "conv12": (1, 'sa_conv', 512, 512, True, 16842752, 7398752256, 21725184, 1568, 512, 4608, False, 0, 0),
    "conv13": (1, 'sa_conv', 512, 512, True, 14434304, 7398752256, 21037056, 1568, 512, 4608, True, 2, 2),
    },
}


def _conv_plans(net: str, bytes_w: int) -> dict[str, ConvPlan]:
    spec, _ = cnn.NETWORKS[net]
    stats = {l.name: l for l in cnn.network_stats(net)}
    out, n = {}, 0
    for i, s in enumerate(spec):
        if s.kind != "conv":
            continue
        n += 1
        nxt = spec[i + 1]
        pool = PoolSpec(nxt.kernel, nxt.stride) if nxt.kind == "pool" \
            else None
        h, w, c = stats[f"conv{n}"].ifm
        out[f"conv{n}"] = plan_conv(
            8, h + 2 * s.pad, w + 2 * s.pad, c, s.kernel, s.kernel,
            s.out_ch, stride=s.stride, bytes_in=4, bytes_w=bytes_w,
            pool=pool, act=s.act)
    return out


@pytest.mark.parametrize("net,bytes_w", sorted(PINNED))
def test_whole_map_plans_are_unchanged(net, bytes_w):
    plans = _conv_plans(net, bytes_w)
    want = PINNED[(net, bytes_w)]
    for name, fields in want.items():
        plan = plans[name]
        assert _fields(plan) == fields, name
        assert (plan.band, plan.bands) == (0, 1), name
    assert set(plans) - set(want) == ({"conv1"} if net == "vgg16" else set())


def test_verifier_passes_the_banded_stem_and_catches_a_short_halo():
    """The static verifier checks a banded plan's geometry: VGG-16's stem
    verifies clean; the same launch with a window one halo row short
    (a seeded mutation) is caught."""
    sched = LayerSchedule.compile_cnn("vgg16", batch=8, stage="conv",
                                      policy=Engine().policy)
    (key, plan), = [(k, p) for k, p in sched.conv_entries.items()
                    if k.name == "conv1"]
    assert plan.bands == 2
    ctx = context_for(key, plan, sched.policy)
    assert check_coverage(ctx) == []
    x = ctx.geom.input("x")
    short = dataclasses.replace(x, block=(1, x.block[1] - 1, *x.block[2:]))
    geom = dataclasses.replace(ctx.geom, inputs=(short, *ctx.geom.inputs[1:]))
    found = check_coverage(dataclasses.replace(ctx, geom=geom))
    assert any("halo" in f.message for f in found), found


def test_verifier_catches_a_band_that_splits_a_fused_pool():
    policy = DispatchPolicy(vmem_budget=600 * 1024)
    plan = plan_conv(2, 18, 18, 3, 3, 3, 16, bytes_in=4, bytes_w=4,
                     pool=PoolSpec(2, 2), act="relu",
                     vmem_budget=policy.effective_vmem_budget)
    assert plan.bands > 1 and plan.fuse_pool
    key = ConvOpKey("c", 2, 18, 18, 3, 3, 3, 16, 1, "float32", "float32",
                    pool_window=2, pool_stride=2)
    ctx = context_for(key, plan, policy)
    assert check_coverage(ctx) == []
    odd = dataclasses.replace(plan, band=plan.band - 1)
    found = check_coverage(dataclasses.replace(ctx, plan=odd))
    assert any("straddles" in f.message for f in found), found


@pytest.mark.parametrize("budget,banded", [(3_000_000, {"conv1"}),
                                           (1_433_600, {"conv1", "conv2"})])
def test_vgg16_zoo_serves_banded_layers_like_the_reference(budget, banded):
    """VGG-16 through the zoo at 32x32, width 0.125, under budgets that
    band the stem (and conv2 with its fused 2x2/2 pool): every request is
    served and its logits match the XLA oracle; the conv stack's features
    are bitwise those of whole-slab plans."""
    from repro.serve.zoo import ModelZooServer, ZooRequest, build_zoo

    eng = Engine(backend="pallas", policy=DispatchPolicy(vmem_budget=budget))
    model, = build_zoo(("vgg16",), seed=3, in_res={"vgg16": 32},
                       width_mult=0.125, max_batch=4, engine=eng)
    zoo = ModelZooServer([model])
    reqs = [ZooRequest(uid=i, model="vgg16", image=np.asarray(
        _rand(10 + i, (32, 32, 3)))) for i in range(3)]
    for r in reqs:
        zoo.submit(r)
    zoo.serve()
    assert [r.status for r in reqs] == ["served"] * 3
    conv, _ = model.server._stage_schedules(3)
    assert {k.name for k, p in conv.conv_entries.items()
            if p.bands > 1} == banded
    x = jnp.stack([jnp.asarray(r.image) for r in reqs])
    want = cnn.cnn_forward("vgg16", model.params, x, backend="xla")
    for r, w in zip(reqs, want):
        np.testing.assert_allclose(r.logits, w, rtol=1e-4, atol=1e-4)
    feats = [cnn.cnn_conv_stage("vgg16", model.params, x, eng=e)
             for e in (eng, Engine(backend="pallas"))]
    np.testing.assert_array_equal(*map(np.asarray, feats))
