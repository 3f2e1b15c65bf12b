"""What the per-layer metric files read a finished run with.

Each ``bench/metrics/<name>.py`` is a few lines that call one of these on
the ``Run`` the harness hands it.  A reader returns ``None`` where the run
holds nothing to read: no wave in the window, no trace, no device time of
the kernel it looks for.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from bench import flops, trace


@dataclass
class Run:
    """A finished traced run.  Its window has two halves.  The first runs
    untraced: the host spans, wave decisions and requests served there
    give the host-side metrics, undisturbed by the profiler.  The second
    runs under the profiler: its device trace and its waves give the
    device-side metrics."""
    cfg: dict
    peak: dict
    chips: int
    spans: object                   # system.Spans
    host_lo_ns: int                 # the untraced half, perf_counter clock
    host_hi_ns: int
    host_served: int                # requests served in the untraced half
    host_decisions: list            # (rows, cooperative) per wave
    traced_decisions: list = field(default_factory=list)
    events: list = field(default_factory=list)   # trace.Event
    trace_lo: int = 0               # the traced half, trace clock
    trace_hi: int = 0
    planes: list = field(default_factory=list)   # device planes in use

    @property
    def host_seconds(self) -> float:
        return (self.host_hi_ns - self.host_lo_ns) / 1e9


def sched_host_ms_per_wave(run: Run) -> float | None:
    """Host time in ``serve()`` outside the waves' executor calls, per
    wave, in ms."""
    if not run.host_decisions:
        return None
    serve = run.spans.total_ns("serve", run.host_lo_ns, run.host_hi_ns)
    execute = run.spans.total_ns("step_wave", run.host_lo_ns,
                                 run.host_hi_ns)
    return (serve - execute) / len(run.host_decisions) / 1e6


def rows_per_wave(run: Run) -> float | None:
    if not run.host_decisions:
        return None
    return sum(r for r, _ in run.host_decisions) / len(run.host_decisions)


def mfu(run: Run) -> float | None:
    """Images per second times operations per image over the peak of the
    chips in use, percent."""
    if not run.host_served or run.host_seconds <= 0:
        return None
    rate = run.host_served / run.host_seconds * flops.flops_per_image(run.cfg)
    return 100.0 * rate / (run.peak["mxu_flops_per_s"] * run.chips)


def kernel_roofline(run: Run, kind: str, pattern: str) -> float | None:
    """The least time of the ``kind`` layers of every wave of the traced
    half over the device time of the kernel events matching ``pattern``,
    percent.  Only per-replica waves are counted: the rows of a
    cooperative wave are split over chips."""
    if not run.planes or any(coop for _, coop in run.traced_decisions):
        return None
    spent = trace.family_ns(run.events, pattern, run.trace_lo, run.trace_hi)
    if spent <= 0:
        return None
    least = sum(flops.least_seconds(run.cfg, rows, kind, run.peak)
                for rows, _ in run.traced_decisions)
    return 100.0 * least / (spent / 1e9)


def busy_s(run: Run) -> float | None:
    """Seconds of the traced half in which an operation ran, averaged over
    the devices in use."""
    if not run.planes:
        return None
    return sum(trace.busy_ns(run.events, p, run.trace_lo, run.trace_hi)
               for p in run.planes) / len(run.planes) / 1e9


def device_idle_share(run: Run) -> float | None:
    busy = busy_s(run)
    span = (run.trace_hi - run.trace_lo) / 1e9
    if busy is None or span <= 0:
        return None
    return 100.0 * (1.0 - busy / span)
