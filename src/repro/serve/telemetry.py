"""Host spans and counters inside the serving path, on a real clock.

The zoo, the fleet and the wave executor mark their layer boundaries with
:func:`span` and :func:`count`.  A span records ``(name, start_ns,
end_ns, parent, ident)`` on ``time.perf_counter_ns()``:

* ``parent`` is the sequence number of the span that was open around it
  on the same thread, ``-1`` at the top;
* ``ident`` ties the spans of one unit of work together: the executor's
  wave index for ``cnn.*`` spans, the ``serve()`` call index for
  ``zoo.*`` and ``fleet.*`` spans.

Each span is also entered as a ``jax.profiler.TraceAnnotation`` of the
same name, so a profiler session shows it on the trace's clock beside the
device's kernels.

Records go into a ring of fixed size, allocated once as integer columns
plus a table of names: recording keeps no Python object per span, and the
ring's memory does not grow with the number of waves served.
:func:`records` returns ``None`` for a window the ring has overwritten
part of, so a reader never reports a partial window.

A counter is keyed by its name and by the name of the innermost span
open on the counting thread (``None`` outside any span).  ``compile``
counts the backend compiles JAX reports through ``jax.monitoring``, so it
says which step compiled.  ``cnn.stage_calls`` counts the calls of a
CNN stage's compiled program and ``cnn.stage_builds`` the traces that
built one (:mod:`repro.models.cnn`): after warm-up a wave adds one call
per stage and no build.

The spans observe the serving path; no scheduling decision reads them.
There is one recorder per process, so that the layers need no handle
passed through their constructors and a caller that never heard of it
still gets its spans.  Recording is on from import; :func:`disable` turns
it off (a span is then one shared no-op context) and :func:`enable` back
on.
"""
from __future__ import annotations

import threading
import time
from array import array
from typing import NamedTuple

import jax
import numpy as np

#: records the ring holds (a power of two).  The busiest serving loop
#: measured, an open loop on one TPU v5e, cuts about 70 waves a second at
#: about 14 records a wave: the ring holds over two minutes of it.
CAPACITY = 1 << 17

#: the ``jax.monitoring`` event counted as ``compile``
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    """One closed span, as :func:`records` returns it."""
    seq: int                 # the span's sequence number
    name: str
    start_ns: int            # time.perf_counter_ns()
    end_ns: int
    parent: int              # seq of the enclosing span, -1 at the top
    ident: int               # wave or serve() call index, -1 if none


class _NoSpan:
    """The span handed out while recording is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


class _OpenSpans(threading.local):
    """Each thread's stack of open spans: ``(seq, name)``."""

    def __init__(self) -> None:
        self.stack: list[tuple[int, str]] = []


class _Span:
    __slots__ = ("_rec", "_name", "_ident", "_seq", "_ann")

    def __init__(self, rec: Recorder, name: str, ident: int) -> None:
        self._rec, self._name, self._ident = rec, name, ident

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        self._seq = self._rec._open(self._name, self._ident)
        return self

    def __exit__(self, *exc) -> bool:
        self._rec._close(self._seq)
        self._ann.__exit__(*exc)
        return False


class Recorder:
    """A fixed ring of span records and a table of counters."""

    def __init__(self, capacity: int = CAPACITY) -> None:
        if capacity <= 0 or capacity & (capacity - 1):
            raise ValueError(f"capacity must be a power of two, "
                             f"got {capacity}")
        self.capacity = capacity
        self._mask = capacity - 1
        zeros = bytes(8 * capacity)
        self._seq = array("q", zeros)
        self._start = array("q", zeros)
        self._end = array("q", [-1]) * capacity      # -1: open or unused
        self._parent = array("q", zeros)
        self._ident = array("q", zeros)
        self._name = array("i", bytes(4 * capacity))
        self._names: list[str] = []
        self._name_ids: dict[str, int] = {}
        self._next = 0
        # the latest instant an overwritten record covered
        self._lost_ns = -1
        self._counts: dict[tuple[str, str | None], int] = {}
        self._open_spans = _OpenSpans()
        self._lock = threading.Lock()
        self._on = True

    # -- switching ------------------------------------------------------------
    def enable(self) -> None:
        self._on = True

    def disable(self) -> None:
        """Record nothing until :meth:`enable`; spans already open still
        close into the ring."""
        self._on = False

    @property
    def enabled(self) -> bool:
        return self._on

    # -- recording ------------------------------------------------------------
    def span(self, name: str, ident: int = -1):
        """A context manager that records one span named ``name``."""
        if not self._on:
            return _NO_SPAN
        return _Span(self, name, ident)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the counter ``name`` of the innermost open span."""
        if not self._on:
            return
        stack = self._open_spans.stack
        key = (name, stack[-1][1] if stack else None)
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + n

    def _open(self, name: str, ident: int) -> int:
        stack = self._open_spans.stack
        with self._lock:
            seq = self._next
            self._next = seq + 1
            slot = seq & self._mask
            if seq >= self.capacity:            # the oldest record goes
                old = self._end[slot]
                self._lost_ns = max(self._lost_ns, old if old >= 0
                                    else time.perf_counter_ns())
            nid = self._name_ids.get(name)
            if nid is None:
                nid = self._name_ids[name] = len(self._names)
                self._names.append(name)
            self._seq[slot] = seq
            self._name[slot] = nid
            self._parent[slot] = stack[-1][0] if stack else -1
            self._ident[slot] = ident
            self._end[slot] = -1
            self._start[slot] = time.perf_counter_ns()
        stack.append((seq, name))
        return seq

    def _close(self, seq: int) -> None:
        t = time.perf_counter_ns()
        self._open_spans.stack.pop()
        slot = seq & self._mask
        with self._lock:
            if self._seq[slot] == seq:
                self._end[slot] = t
            else:                   # overwritten while it was open
                self._lost_ns = max(self._lost_ns, t)

    # -- reading --------------------------------------------------------------
    def records(self, lo_ns: int = 0,
                hi_ns: int | None = None) -> list[Span] | None:
        """The closed spans that lie inside ``[lo_ns, hi_ns]``, in the
        order they opened; ``None`` if the ring has overwritten a record
        that reached past ``lo_ns``."""
        with self._lock:
            if self._lost_ns > lo_ns:
                return None
            seq = np.array(self._seq, dtype=np.int64)
            start = np.array(self._start, dtype=np.int64)
            end = np.array(self._end, dtype=np.int64)
            parent = np.array(self._parent, dtype=np.int64)
            ident = np.array(self._ident, dtype=np.int64)
            name = np.array(self._name, dtype=np.int32)
            names = list(self._names)
        keep = (end >= 0) & (start >= lo_ns)
        if hi_ns is not None:
            keep &= end <= hi_ns
        idx = np.flatnonzero(keep)
        idx = idx[np.argsort(seq[idx], kind="stable")]
        return [Span(int(seq[i]), names[name[i]], int(start[i]),
                     int(end[i]), int(parent[i]), int(ident[i]))
                for i in idx]

    def counts(self) -> dict[tuple[str, str | None], int]:
        """Every counter since the process started, keyed by ``(name,
        innermost span)``."""
        with self._lock:
            return dict(self._counts)


_RECORDER = Recorder()
span = _RECORDER.span
count = _RECORDER.count
records = _RECORDER.records
counts = _RECORDER.counts
enable = _RECORDER.enable
disable = _RECORDER.disable


def _on_event(event: str, duration_secs: float, **_) -> None:
    if event == COMPILE_EVENT:
        _RECORDER.count("compile")


jax.monitoring.register_event_duration_secs_listener(_on_event)
