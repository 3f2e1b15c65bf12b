"""Reduce a profiler trace to busy time, kernel time and idle gaps.

The trace is first flattened to plain events, ``(plane, line, name,
start_ns, end_ns)``: the device operations of each TPU (the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane) and the benchmark's own host
annotations (``bench/<span>``).  Everything below works on that list, so
a small recorded list is enough to test it.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    end_ns: int


def load(log_dir: str) -> list[Event]:
    """Device operations and benchmark annotations of the one
    ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if not device and not e.name.startswith(SPAN_PREFIX):
                    continue
                s = int(e.start_ns)
                out.append(Event(plane.name, line.name, e.name, s,
                                 s + int(e.duration_ns)))
    return out


def device_planes(events: list[Event]) -> list[str]:
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)},
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def ops(events: list[Event], plane: str, lo: int, hi: int) -> list[Event]:
    """Device operations of ``plane`` that start inside ``[lo, hi)``."""
    return [e for e in events if e.plane == plane and lo <= e.start_ns < hi]


def spans(events: list[Event], name: str) -> list[Event]:
    return [e for e in events if e.name == SPAN_PREFIX + name]


def window(events: list[Event]) -> tuple[int, int] | None:
    """The traced part of the window: the ``bench/traced`` annotation."""
    w = spans(events, "traced")
    return (w[0].start_ns, w[0].end_ns) if len(w) == 1 else None


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """The union of ``[start, end)`` intervals, as sorted disjoint ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: list[Event], plane: str, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` in which some operation ran on
    ``plane``."""
    return sum(min(e, hi) - max(s, lo) for s, e in union(
        [(x.start_ns, x.end_ns) for x in ops(events, plane, lo, hi)])
        if min(e, hi) > max(s, lo))


def family_ns(events: list[Event], pattern: str, lo: int, hi: int) -> int:
    """Summed device time of the operations whose name matches the regular
    expression ``pattern``, over every device plane."""
    rx = re.compile(pattern)
    return sum(e.end_ns - e.start_ns for p in device_planes(events)
               for e in ops(events, p, lo, hi) if rx.search(e.name))


def short_name(name: str) -> str:
    """An HLO operation's trace name without operands and layouts:
    ``%sa_fc_matmul.1 = f32[16,4096] custom-call``."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    rhs = re.sub(r"\{[^{}]*\}", "", rhs).split(" ")
    return f"{lhs} = {rhs[0]} {rhs[1].split('(')[0]}" if len(rhs) > 1 \
        else f"{lhs} = {rhs[0]}"


def top_ops(events: list[Event], lo: int, hi: int,
            n: int = 10) -> list[list]:
    """The ``n`` operations (by :func:`short_name`) with the most device
    time, averaged over the device planes, in seconds."""
    planes = device_planes(events)
    tot: dict[str, int] = defaultdict(int)
    for p in planes:
        for e in ops(events, p, lo, hi):
            tot[short_name(e.name)] += e.end_ns - e.start_ns
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / max(1, len(planes))] for k, v in ranked]


#: which host activity an idle gap belongs to, innermost span first
GAP_OWNERS = ("conv_dispatch", "fc_dispatch", "step_wave", "serve")


def idle_by_span(events: list[Event], lo: int, hi: int) -> list[list]:
    """Idle device time inside ``[lo, hi)``, averaged over the device
    planes, split by the benchmark span the host was in at each idle
    instant: ``conv_dispatch``, ``fc_dispatch``, ``sync`` (in a wave's
    executor outside both dispatches: the wait for the logits and the
    wave's bookkeeping), ``scheduler`` (in ``serve()`` outside a wave) or
    ``generator`` (outside ``serve()``).  Seconds, largest first."""
    planes = device_planes(events)
    owners = {n: union([(e.start_ns, e.end_ns) for e in spans(events, n)])
              for n in GAP_OWNERS}
    label = {"conv_dispatch": "conv_dispatch", "fc_dispatch": "fc_dispatch",
             "step_wave": "sync", "serve": "scheduler"}
    tot: dict[str, int] = defaultdict(int)
    for p in planes:
        busy = union([(e.start_ns, e.end_ns) for e in ops(events, p, lo, hi)])
        idle = _complement(busy, lo, hi)
        for n in GAP_OWNERS:
            inside = _intersect(idle, owners[n])
            tot[label[n]] += sum(e - s for s, e in inside)
            idle = _subtract(idle, owners[n])
        tot["generator"] += sum(e - s for s, e in idle)
    ranked = sorted(((k, v) for k, v in tot.items() if v > 0),
                    key=lambda kv: -kv[1])
    return [[k, v / 1e9 / max(1, len(planes))] for k, v in ranked[:10]]


def _complement(iv: list[tuple[int, int]], lo: int,
                hi: int) -> list[tuple[int, int]]:
    out, t = [], lo
    for s, e in iv:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _intersect(a: list[tuple[int, int]],
               b: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a: list[tuple[int, int]],
              b: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            bs, be = b[k]
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            if s >= e:
                break
            k += 1
        if s < e:
            out.append((s, e))
    return out
