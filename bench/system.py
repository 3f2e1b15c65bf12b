"""The system under test behind one small interface, and the host spans
the benchmark records around its calls into the program's layers.

One chip: a ``ModelZooServer`` holding one ``ZooModel``.  Four chips: a
``FleetServer`` of that model over ``replicas`` devices, cooperative
sharded waves on.  Both are driven through their public ``submit`` and
``serve``.  Requests carry the image alone; which pool image it is never
reaches the program.

Spans (``Spans``) are taken on the host clock around calls into the
program and, in a traced run, also written into the profiler's trace as
``bench/<name>`` annotations so device gaps can be matched to them:

* ``serve``: one ``serve()`` call (scheduler, executor, bookkeeping);
* ``step_wave``: one wave's executor call, ``CNNServer.step_wave`` or a
  fleet's cooperative-wave executor;
* ``conv_dispatch`` / ``fc_dispatch``: the calls to the model's conv and
  FC stage functions inside a wave (the host enqueues kernels there);
* ``window``: the measured window; ``traced``: its traced second half.
"""
from __future__ import annotations

import contextlib
import functools
import time

import numpy as np


class Spans:
    """Host spans in memory: ``(name, start_ns, end_ns)`` on the
    ``perf_counter`` clock, and profiler annotations when ``annotate``."""

    def __init__(self) -> None:
        self.records: list[tuple[str, int, int]] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench/{name}")
            ann.__enter__()
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter_ns()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def wrap(self, name: str, fn):
        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        return wrapped

    def total_ns(self, name: str, lo: int, hi: int) -> int:
        return sum(e - s for n, s, e in self.records
                   if n == name and s >= lo and e <= hi)


def _program_params(params: list[dict]) -> list[dict]:
    """The benchmark's weights in the program's layout: an int8
    ``(q, scale)`` pair becomes the program's ``QTensor``."""
    from repro.core.quant import QTensor
    return [{k: QTensor(*v) if isinstance(v, tuple) else v
             for k, v in p.items()} for p in params]


class System:
    """Build the cell's server, warm up its shapes, serve requests."""

    def __init__(self, cfg: dict, mix, params: list[dict], spans: Spans):
        from repro.configs.registry import get_zoo_model
        from repro.serve.zoo import ModelZooServer, ZooModel

        self.cfg, self.mix, self.spans = cfg, mix, spans
        spec = get_zoo_model(cfg["zoo_model"])
        self.model = ZooModel(spec, _program_params(params),
                              in_res=cfg["in_res"],
                              width_mult=cfg["width_mult"],
                              max_batch=cfg["admission_cap"])
        self.cap = self.model.microbatch
        kind = mix.server["kind"]
        if kind == "zoo":
            self.server = ModelZooServer([self.model])
        elif kind == "fleet":
            from repro.serve.fleet import FleetServer
            self.server = FleetServer(
                [self.model], n_replicas=mix.replicas,
                shard_waves=bool(mix.server.get("shard_waves", False)))
        else:
            raise ValueError(f"unknown server kind {kind!r}")
        self.kind = kind
        self.decisions: list[tuple[int, bool]] = []   # (rows, cooperative)
        self.served = 0                                # requests served
        self._uid = 0
        self._installed: list[tuple[object, str, object]] = []

    # -- spans around the program's layers ---------------------------------
    def _patch(self, obj, attr: str, name: str) -> None:
        old = getattr(obj, attr)
        self._installed.append((obj, attr, old))
        setattr(obj, attr, self.spans.wrap(name, old))

    def install_spans(self) -> None:
        """Wrap the executor and stage calls in spans.  Call after
        warm-up, so every fleet lane exists."""
        from repro.models import cnn
        self._patch(cnn, "cnn_conv_stage", "conv_dispatch")
        self._patch(cnn, "cnn_fc_stage", "fc_dispatch")
        if self.kind == "zoo":
            self._patch(self.model.server, "step_wave", "step_wave")
        else:
            for lane in self.server._lanes.values():
                for srv in lane.values():
                    self._patch(srv, "step_wave", "step_wave")
            self._patch(self.server, "_execute_sharded", "step_wave")

    def remove_spans(self) -> None:
        while self._installed:
            obj, attr, old = self._installed.pop()
            setattr(obj, attr, old)

    # -- serving ------------------------------------------------------------
    def serve(self, images: list[np.ndarray]) -> list[np.ndarray | None]:
        """Submit one request per image, run one ``serve()`` call, and
        return each request's logits (``None`` unless it was served)."""
        from repro.serve.zoo import ZooRequest

        reqs = []
        for img in images:
            reqs.append(ZooRequest(uid=self._uid, model=self.model.name,
                                   image=img))
            self._uid += 1
            self.server.submit(reqs[-1])
        with self.spans.span("serve"):
            rep = self.server.serve()
        self.decisions.extend((d.batch, bool(getattr(d, "shards", ())))
                              for d in rep.decisions)
        out = [r.logits if r.status == "served" else None for r in reqs]
        self.served += sum(o is not None for o in out)
        return out

    def warm_up(self, pool: np.ndarray) -> None:
        """Run every wave shape this cell's traffic can cut, twice, so
        nothing compiles or loads inside the window.  A zoo cuts waves of
        1..cap rows from an open loop and of ``cap`` (and the chunk's
        remainder) from a closed one.  A fleet cuts per-replica waves of
        1..cap rows on any replica and cooperative waves of cap+1 ..
        replicas*cap rows."""
        mix, cap = self.mix, self.cap
        for _ in range(2):
            if self.kind == "fleet":
                self._warm_fleet_lanes(pool)
                for n in range(cap + 1, mix.replicas * cap + 1):
                    self.serve([pool[i % len(pool)] for i in range(n)])
                continue
            if mix.loop == "open":
                sizes = range(1, cap + 1)
            else:
                sizes = sorted({min(cap, mix.chunk), mix.chunk % cap} - {0})
            for n in sizes:
                self.serve([pool[i % len(pool)] for i in range(n)])
        self.decisions.clear()
        self.served = 0

    def _warm_fleet_lanes(self, pool: np.ndarray) -> None:
        import jax

        from repro.serve.cnn_server import CNNRequest

        for i, rid in enumerate(self.server.replica_ids):
            lane = self.server._lane(rid, self.model.name)
            dev = self.server.replica_device(i)
            for n in range(1, self.cap + 1):
                for j in range(n):
                    self._uid += 1
                    lane.submit(CNNRequest(
                        uid=-self._uid,
                        image=jax.device_put(pool[j % len(pool)], dev)))
                lane.step_wave()

    def devices(self) -> list:
        import jax
        if self.kind == "fleet":
            return list(self.server.devices())[:self.mix.replicas]
        return [jax.devices()[0]]
