"""One run of one cell: set up, measure a window, check, report.

Set-up makes the weights and the image pool from the seed, builds the
server, and serves every wave shape the mix can cut, so nothing compiles
inside the window.  The window then drives the server with the mix's
traffic.  After it the system is freed and the reference computes the
logits of every pool image that a served request carried; every served
answer of the window is compared with them.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import readers, reference, spec, system, trace, traffic, weights

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: ``max_rel_err`` of a run with no finite answer to compare (JSON has no inf)
MISSING = 1e30


class BenchError(RuntimeError):
    """The run cannot be made here: no result is printed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache, at ``JAX_COMPILATION_CACHE_DIR``
    when that is set and otherwise at ``<checkout>/.jax_cache``: one
    fixed path, so every run of a cell after its first loads its programs."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_devices(chips: int, peaks: dict, require_tpu: bool):
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"platform {d.platform}  device_kind {d.device_kind}  "
        f"count {len(devs)}")
    if require_tpu and d.platform != "tpu":
        raise BenchError(f"no TPU attached (platform {d.platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, found {len(devs)}")
    if d.device_kind not in peaks:
        raise BenchError(f"device kind {d.device_kind!r} has no peaks in "
                         "bench/peaks.json")
    return devs, peaks[d.device_kind]


class _GcPauses:
    """Python's garbage collections while ``active``: count and seconds
    per generation, to tell a collector pause from a slow host."""

    def __init__(self) -> None:
        self.active = False
        self.seconds = [0.0, 0.0, 0.0]
        self.count = [0, 0, 0]
        self.longest = 0.0
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if not self.active:
            return
        if phase == "start":
            self._t = time.perf_counter()
            return
        dt = time.perf_counter() - self._t
        g = info["generation"]
        self.count[g] += 1
        self.seconds[g] += dt
        self.longest = max(self.longest, dt)

    def close(self) -> None:
        gc.callbacks.remove(self._on)


class _CompileCounter:
    """Counts programs compiled or loaded while ``active``."""

    def __init__(self) -> None:
        import jax
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event == COMPILE_EVENT:
            self.count += 1


def _closed_window(sut, mix, pool, seconds: float, seed: int,
                   half=None):
    ids_all = traffic.image_ids(1 << 20, mix.pool, seed)
    got, ids = [], []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if half is not None and time.perf_counter() - t0 >= seconds / 2:
            half, _ = None, half()
        chunk = ids_all[len(ids):len(ids) + mix.chunk]
        got.extend(sut.serve([pool[i] for i in chunk]))
        ids.extend(int(i) for i in chunk)
    return np.asarray(ids), got, None, time.perf_counter() - t0


def _open_window(sut, mix, pool, seconds: float, seed: int, half=None):
    due = traffic.due_times(mix, seconds, seed)
    ids = traffic.image_ids(len(due), mix.pool, seed)
    got: list = [None] * len(due)
    done = np.full(len(due), np.nan)
    late: list[float] = []
    nxt = 0
    t0 = time.perf_counter()
    while nxt < len(due):
        now = time.perf_counter() - t0
        if half is not None and now >= seconds / 2:
            half, _ = None, half()
            continue
        if due[nxt] > now:
            time.sleep(due[nxt] - now)
            late.append(time.perf_counter() - t0 - due[nxt])
            continue
        end = int(np.searchsorted(due, now, side="right"))
        out = sut.serve([pool[i] for i in ids[nxt:end]])
        t = time.perf_counter() - t0
        for k, logits in enumerate(out, start=nxt):
            got[k] = logits
            done[k] = t if logits is not None else np.nan
        nxt = end
    span = time.perf_counter() - t0
    lat_ms = np.where(np.isnan(done), span, done) - due
    log(f"generator: {len(late)} sleeps, woke late by max "
        f"{max(late, default=0.0) * 1e3:.3f} ms, mean "
        f"{np.mean(late) * 1e3 if late else 0.0:.3f} ms")
    return ids, got, lat_ms * 1e3, span


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def compare(cfg: dict, params, pool: np.ndarray, ids: np.ndarray,
            got: list) -> float:
    """Largest ``max|served - reference| / max|reference|`` over the
    served answers; the reference runs once per pool image used.  With
    no answer to compare, or a non-finite one, it reads ``MISSING``."""
    used = sorted({int(i) for i, g in zip(ids, got) if g is not None})
    if not used:
        return MISSING
    ref = reference.logits(cfg["layers"], weights.for_reference(params),
                           pool[used])
    row = {u: k for k, u in enumerate(used)}
    worst = 0.0
    for i, g in zip(ids, got):
        if g is None:
            continue
        r = ref[row[int(i)]]
        err = float(np.max(np.abs(np.asarray(g) - r))
                    / max(float(np.max(np.abs(r))), 1e-30))
        worst = max(worst, err) if np.isfinite(err) else MISSING
    return worst


def verdict(cfg: dict, params, pool: np.ndarray, ids: np.ndarray,
            got: list) -> tuple[bool, dict]:
    """``correct`` and the numbers it was decided on, each beside its
    limit: every request answered, and every answer within the
    configuration's ``max_rel_err`` of the reference."""
    err = compare(cfg, params, pool, ids, got)
    limit = float(cfg["correct"]["max_rel_err"])
    unserved = sum(g is None for g in got)
    checks = {"max_rel_err": {"value": err, "limit": limit},
              "unserved": {"value": unserved, "limit": 0}}
    return bool(unserved == 0 and err <= limit), checks


def _longest_serve(spans: system.Spans, lo: int) -> str:
    """Where the longest ``serve()`` call of the window spent its time:
    inside the waves' executor calls and their stage dispatches, or in
    the scheduler around them."""
    _, s0, e0 = max((r for r in spans.records
                     if r[0] == "serve" and r[1] >= lo),
                    key=lambda r: r[2] - r[1])
    ms = {k: [(e - s) / 1e6 for n, s, e in spans.records
              if n == k and s >= s0 and e <= e0]
          for k in ("step_wave", "conv_dispatch", "fc_dispatch")}
    whole, waves = (e0 - s0) / 1e6, sum(ms["step_wave"])
    return (f"longest serve() {whole:.2f} ms at {(s0 - lo) / 1e9:.3f} s: "
            f"{len(ms['step_wave'])} waves, longest "
            f"{max(ms['step_wave'], default=0.0):.2f} ms; step_wave "
            f"{waves:.2f} ms (conv_dispatch {sum(ms['conv_dispatch']):.2f}, "
            f"fc_dispatch {sum(ms['fc_dispatch']):.2f}), scheduler "
            f"{whole - waves:.2f} ms")


def run(cell: spec.Cell, *, seed: int, seconds: float, traced: bool,
        t0: float, require_tpu: bool = True,
        peaks: dict | None = None) -> dict:
    """Run ``cell`` once and return its result line as a dict."""
    import jax

    cache = enable_compile_cache(spec.ROOT)
    if peaks is None:
        peaks = json.loads((spec.BENCH / "peaks.json").read_text())
    devs, peak = check_devices(cell.chips, peaks, require_tpu)
    cfg, mix = cell.config, traffic.load(cell.traffic)
    counter = _CompileCounter()
    log(f"compile_cache {cache}")

    t = time.perf_counter()
    params = jax.block_until_ready(weights.make(cfg, seed))
    pool = weights.images(cfg, seed, mix.pool)
    t_weights = time.perf_counter() - t
    spans = system.Spans()
    sut = system.System(cfg, mix, params, spans)
    t_build = time.perf_counter() - t - t_weights
    sut.warm_up(pool)
    sut.install_spans()
    t_warm = time.perf_counter() - t - t_weights - t_build
    log(f"setup: weights+images {t_weights:.3f} s, build {t_build:.3f} s, "
        f"warm-up {t_warm:.3f} s, micro-batch {sut.cap}")

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    marks: dict = {}

    def half() -> None:
        """Half-way through a traced run: close the untraced half and
        start the profiler for the rest."""
        marks.update(split=len(sut.decisions), served=sut.served,
                     mid_ns=time.perf_counter_ns())
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        spans.annotate = True
        marks["traced"] = jax.profiler.TraceAnnotation("bench/traced")
        marks["traced"].__enter__()

    setup_s = time.perf_counter() - t0
    pauses = _GcPauses()
    counter.active = pauses.active = True
    window = _closed_window if mix.loop == "closed" else _open_window
    with spans.span("window"):
        ids, got, lat_ms, span = window(sut, mix, pool, seconds, seed,
                                        half if traced else None)
    counter.active = pauses.active = False
    pauses.close()
    lo = next(s for n, s, e in spans.records if n == "window")
    serves = [e - s for n, s, e in spans.records if n == "serve" and s >= lo]
    if traced and "traced" not in marks:
        raise BenchError("the window ended before its traced half began")
    if traced:
        marks["traced"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        spans.annotate = False
    served = sum(g is not None for g in got)
    log(f"window: {len(got)} requests, {served} served, "
        f"{len(sut.decisions)} waves, {span:.3f} s; "
        f"compiles in window {counter.count}; {len(serves)} serve() calls, "
        f"median {np.median(serves) / 1e6:.2f} ms, longest "
        f"{max(serves) / 1e6:.2f} ms; gc collections {pauses.count}, "
        f"{[round(x, 4) for x in pauses.seconds]} s, longest "
        f"{pauses.longest * 1e3:.2f} ms")
    log(_longest_serve(spans, lo))

    memory = _memory_peak(sut.devices())
    used = sut.devices()
    result: dict = {"correct": False, "attempted": len(got),
                    "failed": len(got) - served, "metrics": {},
                    "device": {"platform": devs[0].platform,
                               "kind": devs[0].device_kind,
                               "count": len(devs),
                               "memory_peak_bytes": memory}}
    run_ = readers.Run(cfg=cfg, peak=peak, chips=len(used),
                       spans=spans, host_lo_ns=lo,
                       host_hi_ns=marks.get("mid_ns", lo),
                       host_served=marks.get("served", 0),
                       host_decisions=sut.decisions[:marks.get("split", 0)],
                       traced_decisions=sut.decisions[
                           marks.get("split", 0):])
    sut.remove_spans()
    del sut
    gc.collect()

    e2e = {m["name"]: m for m in cell.end_to_end}
    if not traced:
        values = {"setup_s": setup_s}
        if lat_ms is not None:
            values["latency_p50_ms"] = float(np.percentile(lat_ms, 50))
            values["latency_p95_ms"] = float(np.percentile(lat_ms, 95))
        if served:
            values["images_per_s"] = served / span
        for name, m in e2e.items():
            if name in values:
                result["metrics"][name] = {"value": values[name],
                                           "unit": m["unit"]}
    else:
        _read_trace(run_, log_dir, used, result, cell)
        shutil.rmtree(log_dir, ignore_errors=True)
    result["compiles_in_window"] = counter.count

    result["correct"], result["checks"] = verdict(cfg, params, pool, ids, got)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def _read_trace(run_: readers.Run, log_dir: str, used: list,
                result: dict, cell: spec.Cell) -> None:
    events = trace.load(log_dir)
    win = trace.window(events)
    planes = trace.device_planes(events)
    mine = [p for p in planes
            if int(trace.DEVICE_PLANE.match(p).group(1))
            in {d.id for d in used}]
    if win is not None:
        run_.events, (run_.trace_lo, run_.trace_hi) = events, win
        run_.planes = mine or planes
    for m in cell.per_layer:
        value = spec.reader(m["name"])(run_)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    busy = readers.busy_s(run_)
    if busy is not None:
        result["device"]["busy_s"] = busy
        result["device"]["window_s"] = (run_.trace_hi - run_.trace_lo) / 1e9
        result["breakdown"] = {
            "device_ops": trace.top_ops(events, run_.trace_lo, run_.trace_hi),
            "idle_gaps": trace.idle_by_span(
                [e for e in events if e.plane in run_.planes
                 or not trace.DEVICE_PLANE.match(e.plane)],
                run_.trace_lo, run_.trace_hi)}
    log(f"trace: {len(events)} events, planes {planes}, window {win}")
