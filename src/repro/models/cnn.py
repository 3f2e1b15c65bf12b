"""CNNs — the paper's own evaluation domain (AlexNet, VGG-16).

Layer tables match the originals exactly (they reproduce the paper's
Table I MAC/weight counts; asserted in tests/test_perf_model.py).  The
forward pass runs every CONV on the SA-CONV dataflow (implicit GEMM —
patch extraction inside the kernel, no materialized im2col), every FC on
SA-FC when memory-bound, and every conv+maxpool pair as one fused dispatch
whose pooling-&-activation stage rides the accumulator-flush epilogue —
i.e. the complete MPNA operator set with the Fig. 7 pipeline intact.

Each stage (the conv stack, the classifier head) is traced once per
input shape through the engine into one ``jax.jit`` program, with the
parameters as its arguments; every later call of that shape is one
program call, and the engine dispatch records of the trace are replayed
into the caller's trace.  :mod:`repro.serve.telemetry` counts
``cnn.stage_calls`` per call and ``cnn.stage_builds`` per trace.
"""
from __future__ import annotations

from dataclasses import dataclass
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.dataflow import PoolSpec
from repro.models.layers import dense_init
from repro.serve import telemetry


@dataclass(frozen=True)
class ConvSpec:
    kind: str                  # conv | pool | fc
    out_ch: int = 0
    kernel: int = 0
    stride: int = 1
    pad: int = 0
    act: str = "relu"


# AlexNet (227x227x3 input, no grouping — matches Table I: 1.07B CONV MACs,
# 58.6M FC MACs, 3.74M CONV weights, 58.6M FC weights)
ALEXNET: tuple[ConvSpec, ...] = (
    ConvSpec("conv", 96, 11, 4, 0),
    ConvSpec("pool", kernel=3, stride=2),
    ConvSpec("conv", 256, 5, 1, 2),
    ConvSpec("pool", kernel=3, stride=2),
    ConvSpec("conv", 384, 3, 1, 1),
    ConvSpec("conv", 384, 3, 1, 1),
    ConvSpec("conv", 256, 3, 1, 1),
    ConvSpec("pool", kernel=3, stride=2),
    ConvSpec("fc", 4096),
    ConvSpec("fc", 4096),
    ConvSpec("fc", 1000, act="none"),
)

# VGG-16 (224x224x3): 15.3B CONV MACs / 123.6M FC MACs
def _vgg():
    spec = []
    for reps, ch in ((2, 64), (2, 128), (3, 256), (3, 512), (3, 512)):
        spec += [ConvSpec("conv", ch, 3, 1, 1)] * reps
        spec += [ConvSpec("pool", kernel=2, stride=2)]
    spec += [ConvSpec("fc", 4096), ConvSpec("fc", 4096),
             ConvSpec("fc", 1000, act="none")]
    return tuple(spec)


VGG16: tuple[ConvSpec, ...] = _vgg()

NETWORKS = {"alexnet": (ALEXNET, 227), "vgg16": (VGG16, 224)}


# ---------------------------------------------------------------------------
# analytical layer statistics (Table I / Fig. 6 reproduction)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class LayerStats:
    name: str
    kind: str                  # conv | fc
    macs: int
    weights: int
    # data-reuse factors (Sec. V-A definitions)
    weight_reuse: int          # uses of one weight = |OF| (conv) / 1 (fc)
    in_act_reuse: int          # uses of one input activation
    out_act_reuse: int         # partial sums per output activation
    ifm: tuple[int, int, int] = (0, 0, 0)    # H, W, C at the layer input
    ofm: tuple[int, int, int] = (0, 0, 0)


def network_stats(name: str, *, in_res: int | None = None,
                  in_ch: int = 3) -> list[LayerStats]:
    spec, res0 = NETWORKS[name]
    res, ch = in_res or res0, in_ch
    out = []
    ci = 0
    for s in spec:
        if s.kind == "conv":
            ci += 1
            o = (res + 2 * s.pad - s.kernel) // s.stride + 1
            macs = o * o * s.out_ch * s.kernel * s.kernel * ch
            w = s.out_ch * s.kernel * s.kernel * ch
            out.append(LayerStats(
                f"conv{ci}", "conv", macs, w,
                weight_reuse=o * o,
                in_act_reuse=s.kernel * s.kernel * s.out_ch,  # approx, interior
                out_act_reuse=s.kernel * s.kernel * ch,
                ifm=(res, res, ch), ofm=(o, o, s.out_ch)))
            res, ch = o, s.out_ch
        elif s.kind == "pool":
            res = (res - s.kernel) // s.stride + 1
        else:  # fc
            fan_in = res * res * ch if res > 1 else ch
            macs = fan_in * s.out_ch
            out.append(LayerStats(
                f"fc{len([l for l in out if l.kind=='fc'])+1}", "fc",
                macs, macs, weight_reuse=1, in_act_reuse=s.out_ch,
                out_act_reuse=fan_in, ifm=(1, 1, fan_in),
                ofm=(1, 1, s.out_ch)))
            res, ch = 1, s.out_ch
    return out


# ---------------------------------------------------------------------------
# classifier head in isolation — the SA-FC workload (paper Fig. 6b: the FC
# stack holds nearly all of AlexNet/VGG's weights at weight reuse 1, so it
# is the batch-amortization target benchmarks/fc_batch.py measures and
# serve/cnn_server.py batches for)
# ---------------------------------------------------------------------------
def fc_head(name: str, *, in_res: int | None = None, in_ch: int = 3,
            width_mult: float = 1.0) -> list[tuple[int, int, str]]:
    """(fan_in, fan_out, act) triples of the network's FC stack, geometry
    from :func:`network_stats` (single source of truth for the shape
    propagation).  ``width_mult`` scales every dimension uniformly (min 8)
    so the chain stays consistent — the wall-clock benchmarks shrink the
    head without changing its shape structure."""
    spec, _ = NETWORKS[name]
    fcs = [s for s in spec if s.kind == "fc"]
    stats = [l for l in network_stats(name, in_res=in_res, in_ch=in_ch)
             if l.kind == "fc"]

    def scale(d: int) -> int:
        return max(8, int(d * width_mult))

    return [(scale(l.ifm[2]), scale(l.ofm[2]), s.act)
            for l, s in zip(stats, fcs)]


def init_fc_head(head: Sequence[tuple[int, int, str]], key, *,
                 dtype=jnp.float32) -> list:
    params = []
    for fan_in, fan_out, _ in head:
        key, k1 = jax.random.split(key)
        params.append({"w": dense_init(k1, fan_in, fan_out, dtype),
                       "b": jnp.zeros((fan_out,), dtype)})
    return params


def fc_head_forward(head: Sequence[tuple[int, int, str]], params: list,
                    x2d: jax.Array, *,
                    eng: engine.Engine | None = None) -> jax.Array:
    """Run just the classifier head: (batch, fan_in) -> logits, every layer
    an engine-dispatched matmul (named fc1.. like :func:`cnn_forward`), so
    the batch-amortized SA-FC plans/trace/schedule apply unchanged."""
    if eng is None:
        eng = engine.current()
    for i, ((_, _, act), p) in enumerate(zip(head, params), start=1):
        x2d = eng.matmul(x2d, p["w"], p["b"], act=act, name=f"fc{i}")
    return x2d


# ---------------------------------------------------------------------------
# functional model (runs on the Pallas kernels)
# ---------------------------------------------------------------------------
def init_cnn(name: str, key, *, in_res: int | None = None, in_ch: int = 3,
             width_mult: float = 1.0, dtype=jnp.float32) -> list:
    spec, res0 = NETWORKS[name]
    res, ch = in_res or res0, in_ch
    params = []
    for s in spec:
        if s.kind == "conv":
            oc = max(8, int(s.out_ch * width_mult))
            key, k1, k2 = jax.random.split(key, 3)
            f = (jax.random.normal(k1, (s.kernel, s.kernel, ch, oc),
                                   jnp.float32)
                 * (s.kernel * s.kernel * ch) ** -0.5).astype(dtype)
            params.append({"f": f, "b": jnp.zeros((oc,), dtype)})
            res = (res + 2 * s.pad - s.kernel) // s.stride + 1
            ch = oc
        elif s.kind == "pool":
            params.append({})
            res = (res - s.kernel) // s.stride + 1
        else:
            oc = max(8, int(s.out_ch * width_mult)) if s.out_ch != 1000 \
                else s.out_ch
            fan_in = res * res * ch if res > 1 else ch
            key, k1 = jax.random.split(key)
            params.append({"w": dense_init(k1, fan_in, oc, dtype),
                           "b": jnp.zeros((oc,), dtype)})
            res, ch = 1, oc
    return params


def conv_stage_len(name: str) -> int:
    """Number of spec/param entries in the CONV stage (everything before
    the first FC layer) — the stage boundary of the dual-array pipeline."""
    spec, _ = NETWORKS[name]
    for i, s in enumerate(spec):
        if s.kind == "fc":
            return i
    return len(spec)


# ---------------------------------------------------------------------------
# the stages as compiled programs
# ---------------------------------------------------------------------------
def _conv_layers(name: str, params: list, x: jax.Array,
                 eng: engine.Engine) -> jax.Array:
    """The conv+fused-pool stack, one engine dispatch per layer."""
    spec, _ = NETWORKS[name]
    end = conv_stage_len(name)
    ci = pi = 0
    i = 0
    while i < end:
        s, p = spec[i], params[i]
        if s.kind == "conv":
            ci += 1
            nxt = spec[i + 1] if i + 1 < len(spec) else None
            if nxt is not None and nxt.kind == "pool":
                x = eng.conv2d(x, p["f"], p["b"], stride=s.stride,
                               pad=s.pad, act=s.act,
                               pool=PoolSpec(nxt.kernel, nxt.stride),
                               name=f"conv{ci}")
                pi += 1
                i += 2
                continue
            x = eng.conv2d(x, p["f"], p["b"], stride=s.stride, pad=s.pad,
                           act=s.act, name=f"conv{ci}")
        else:                                       # standalone pool
            pi += 1
            x = eng.pool(x, window=s.kernel, stride=s.stride,
                         name=f"pool{pi}")
        i += 1
    return x.reshape(x.shape[0], -1)


def _fc_layers(name: str, params: list, feats: jax.Array,
               eng: engine.Engine) -> jax.Array:
    """The classifier head, one engine dispatch per layer."""
    spec, _ = NETWORKS[name]
    start = conv_stage_len(name)
    x = feats
    for fi, (s, p) in enumerate(zip(spec[start:], params[start:]), start=1):
        x = x.reshape(x.shape[0], -1)
        x = eng.matmul(x, p["w"], p["b"], act=s.act, name=f"fc{fi}")
    return x


@dataclass
class _StageProgram:
    """One stage traced once through the engine into one ``jax.jit``
    program.  ``records`` are the dispatches that trace made; ``config``
    holds the engine's policy and schedule, whose identities key it."""
    fn: Callable
    config: tuple
    records: tuple[engine.DispatchRecord, ...] = ()


#: the stage programs built so far, by :func:`_program_key`
_PROGRAMS: dict[tuple, _StageProgram] = {}


def _program_key(layers: Callable, name: str, params: list, x,
                 eng: engine.Engine) -> tuple:
    """What a stage program depends on: the stage and network, the
    input's and the parameters' shapes and dtypes, and the engine's
    backend, policy and schedule (by identity: the program holds them,
    so an identity is never reused while its key is live)."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    return (layers, name, tuple(x.shape), x.dtype, tree,
            tuple((p.shape, p.dtype) for p in leaves),
            eng.backend, id(eng.policy), id(eng.schedule))


def _build(layers: Callable, name: str, eng: engine.Engine
           ) -> _StageProgram:
    backend, policy, schedule = eng.backend, eng.policy, eng.schedule

    def traced(params, x):
        # runs once per trace of the program, not once per call
        telemetry.count("cnn.stage_builds")
        rec = engine.Engine(backend=backend, policy=policy,
                            schedule=schedule, trace=engine.DispatchTrace())
        out = layers(name, params, x, rec)
        prog.records = tuple(rec.trace)
        return out

    prog = _StageProgram(jax.jit(traced), (policy, schedule))
    return prog


def _run_stage(layers: Callable, name: str, params: list, x,
               eng: engine.Engine) -> jax.Array:
    """Run one stage as its compiled program — built on the first call of
    its shape — and replay the dispatches its trace made into ``eng``'s
    live trace.  The parameters are arguments of the program, never
    constants in it."""
    key = _program_key(layers, name, params, x, eng)
    prog = _PROGRAMS.get(key)
    if prog is None:
        prog = _PROGRAMS[key] = _build(layers, name, eng)
    telemetry.count("cnn.stage_calls")
    out = prog.fn(params, x)
    eng.replay(prog.records)
    return out


def cnn_conv_stage(name: str, params: list, x: jax.Array, *,
                   backend: str = "pallas",
                   eng: engine.Engine | None = None) -> jax.Array:
    """The SA-CONV stage of the dual-array pipeline: the conv+fused-pool
    stack, ``(N, H, W, C) -> (N, features)`` flattened for the classifier
    head, run as one compiled program per input shape.  Dispatch-for-
    dispatch identical to the CONV prefix of :func:`cnn_forward` (same op
    names ``conv1..``/``pool1..``, same fused conv+pool pairing), so a
    compiled conv-stage schedule resolves every layer by lookup and the
    composition with :func:`cnn_fc_stage` is bitwise the full forward."""
    if eng is None:
        eng = engine.current().with_(backend=backend)
    return _run_stage(_conv_layers, name, params, x, eng)


def cnn_fc_stage(name: str, params: list, feats: jax.Array, *,
                 backend: str = "pallas",
                 eng: engine.Engine | None = None) -> jax.Array:
    """The SA-FC stage of the dual-array pipeline: the classifier head,
    ``(N, features) -> logits``, run as one compiled program per input
    shape.  Consumes the hand-off buffer :func:`cnn_conv_stage`
    produces; op names ``fc1..`` match the FC suffix of
    :func:`cnn_forward` exactly, so the batch-amortized FCPlans resolve
    from a compiled fc-stage schedule unchanged."""
    if eng is None:
        eng = engine.current().with_(backend=backend)
    return _run_stage(_fc_layers, name, params, feats, eng)


def cnn_forward(name: str, params: list, x: jax.Array, *,
                backend: str = "pallas",
                eng: engine.Engine | None = None) -> jax.Array:
    """x: (N, H, W, C) -> logits (N, classes).

    Supply ``eng`` to run the whole network under an explicit
    :class:`~repro.core.engine.Engine` (its backend then governs the CONV
    kernels too, overriding the ``backend`` arg);
    otherwise one is derived from the ambient engine so an active trace /
    policy / schedule still sees every dispatch.  CONV layers go through
    ``eng.conv2d`` — the implicit-GEMM SA-CONV kernel on the pallas
    backend (no materialized im2col patch matrix), planned/traced like
    every other op and resolvable from a compiled
    :meth:`~repro.core.schedule.LayerSchedule.compile_cnn` schedule.

    Each conv immediately followed by a maxpool is dispatched as ONE fused
    conv+pool op (``pool=PoolSpec(...)``): when the plan accepts, the pool
    rides the SA-CONV accumulator-flush epilogue and the full OFM never
    reaches HBM (the paper's Fig. 7 pipeline); when the plan declines the
    engine itself falls back to conv + standalone pool.  Pools not
    preceded by a conv dispatch through ``eng.pool`` so they too appear in
    the trace/schedule.

    The forward IS the composition of the two pipeline stages
    (:func:`cnn_conv_stage` -> :func:`cnn_fc_stage`), each one compiled
    program per input shape — the same programs the dual-array serving
    pipeline runs and overlaps across waves, so serving changes no
    per-request math."""
    if eng is None:
        eng = engine.current().with_(backend=backend)
    feats = cnn_conv_stage(name, params, x, eng=eng)
    return cnn_fc_stage(name, params, feats, eng=eng)
