"""Per-layer metrics read from the program's own spans.

The serving path records spans on the host clock
(``repro.serve.telemetry``): ``zoo.*`` around the zoo's planning,
execution, integrity guard and accounting, ``cnn.*`` around each wave
(``cnn.wave``) and its upload, stage dispatches and logits wait.  The
readers here take them from the program's recorder after a run.  A
program without the recorder, a window the recorder's ring has partly
overwritten, or a window without a wave reads ``None``.

The host metrics come from the untraced half of the window.  The device
metric puts each span of the traced half on the trace's clock through the
``serve()`` call around it: the harness records that call both in its own
spans (``perf_counter``) and in the trace (``bench/serve``), within
microseconds of each other, so the difference of the two starts is the
offset between the clocks at that call.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

from bench import trace

WAVE = "cnn.wave"
#: idle time outside every program span (the harness, the generator)
OUTSIDE = "outside"


def _recorder():
    """The program's span recorder, ``None`` where it has none."""
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    return telemetry


def program_records(lo_ns: int, hi_ns: int, source=None) -> list | None:
    """The program's spans that lie inside ``[lo_ns, hi_ns]`` of the host
    clock; ``None`` where the recorder is missing or lost part of it.
    ``source`` stands in for the recorder (``records(lo, hi)``)."""
    src = _recorder() if source is None else source
    recs = None if src is None else src.records(lo_ns, hi_ns)
    if recs is None:
        return None
    return [r for r in recs if lo_ns <= r.start_ns and r.end_ns <= hi_ns]


def host_ms_per_wave(run, name: str, source=None) -> float | None:
    """Total time of the program's ``name`` spans in the untraced half
    over the waves there (``cnn.wave`` spans), ms."""
    recs = program_records(run.host_lo_ns, run.host_hi_ns, source)
    if not recs:
        return None
    waves = sum(r.name == WAVE for r in recs)
    spent = [r.end_ns - r.start_ns for r in recs if r.name == name]
    if not waves or not spent:
        return None
    return sum(spent) / waves / 1e6


def traced_host_window(run) -> tuple[int, int] | None:
    """The traced half on the host clock: from the split to the end of
    the harness's ``window`` span."""
    ends = [e for n, _, e in run.spans.records if n == "window"]
    return (run.host_hi_ns, ends[0]) if ends else None


def on_trace_clock(run, records: list) -> list | None:
    """``(record, start, end)`` with each record's interval moved to the
    trace's clock by the offset of the ``serve()`` call that holds it.
    Records outside every traced ``serve()`` call are left out; ``None``
    where the harness's and the trace's calls do not pair up."""
    host = sorted((s, e) for n, s, e in run.spans.records
                  if n == "serve" and s >= run.host_hi_ns)
    dev = sorted(x.start_ns for x in trace.spans(run.events, "serve"))
    if not host or len(host) != len(dev):
        return None
    starts = [s for s, _ in host]
    out = []
    for r in records:
        k = bisect.bisect_right(starts, r.start_ns) - 1
        if k < 0 or r.end_ns > host[k][1]:
            continue
        off = dev[k] - host[k][0]
        out.append((r, r.start_ns + off, r.end_ns + off))
    return out


def idle_by_program_span(run, source=None) -> dict[str, float] | None:
    """Device idle time of the traced half, in percent of the half, by
    the innermost program span open on the host at each idle instant
    (``outside`` where none was), averaged over the device planes.  The
    parts add up to the device idle share."""
    if not run.planes or run.trace_hi <= run.trace_lo:
        return None
    win = traced_host_window(run)
    recs = None if win is None else program_records(*win, source)
    mapped = on_trace_clock(run, recs) if recs else None
    if not mapped:
        return None
    seqs = {r.seq for r, _, _ in mapped}
    kids: dict[int, list] = defaultdict(list)
    for r, s, e in mapped:
        if r.parent in seqs:
            kids[r.parent].append((s, e))
    own: dict[str, list] = defaultdict(list)   # each span less its children
    roots = []
    for r, s, e in mapped:
        if r.parent not in seqs:
            roots.append((s, e))
        own[r.name].extend(trace._subtract([(s, e)],
                                           trace.union(kids[r.seq])))
    own_iv = {n: trace.union(iv) for n, iv in own.items()}
    covered = trace.union(roots)
    tot = dict.fromkeys([*own_iv, OUTSIDE], 0)
    for p in run.planes:
        busy = trace.union([(e.start_ns, e.end_ns) for e in
                            trace.ops(run.events, p, run.trace_lo,
                                      run.trace_hi)])
        idle = trace._complement(busy, run.trace_lo, run.trace_hi)
        for n, iv in own_iv.items():
            tot[n] += sum(e - s for s, e in trace._intersect(idle, iv))
        tot[OUTSIDE] += sum(e - s for s, e in trace._subtract(idle, covered))
    scale = 100.0 / len(run.planes) / (run.trace_hi - run.trace_lo)
    return {n: v * scale for n, v in tot.items()}


def idle_share_in(run, name: str, source=None) -> float | None:
    """Percent of the traced half in which the device was idle and the
    innermost program span was ``name``."""
    split = idle_by_program_span(run, source)
    return None if split is None else split.get(name)
