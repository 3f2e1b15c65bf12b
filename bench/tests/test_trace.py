"""The trace reduction, on a trace recorded on one TPU v5e: three
``serve()`` calls of 16 images to the ``alexnet`` zoo (six waves of 8),
flattened by ``trace.load`` to its device operations and benchmark
annotations."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data" / "v5e_zoo_trace.json"
CONV = r"^%sa_conv_implicit(\.\d+)? = "
FC = r"^%sa_fc_matmul(\.\d+)? = "


@pytest.fixture(scope="module")
def events():
    return [trace.Event(*e) for e in json.loads(DATA.read_text())]


def test_recorded_trace_has_one_device_and_a_window(events):
    assert trace.device_planes(events) == ["/device:TPU:0"]
    lo, hi = trace.window(events)
    assert 0 < lo < hi
    assert len(trace.spans(events, "serve")) == 3
    assert len(trace.spans(events, "step_wave")) == 6


def test_busy_is_the_union_of_operations(events):
    lo, hi = trace.window(events)
    ops = trace.ops(events, "/device:TPU:0", lo, hi)
    busy = trace.busy_ns(events, "/device:TPU:0", lo, hi)
    # brute force over the operations' end points
    points = sorted({p for e in ops for p in (e.start_ns, e.end_ns)})
    want = sum(b - a for a, b in zip(points, points[1:])
               if any(e.start_ns <= a and b <= e.end_ns for e in ops))
    assert busy == want
    assert 0 < busy < hi - lo
    assert busy <= sum(e.end_ns - e.start_ns for e in ops)


def test_kernel_families_count_only_the_custom_calls(events):
    lo, hi = trace.window(events)
    conv = [e for e in events if e.name.startswith("%sa_conv_implicit")]
    fc = [e for e in events if e.name.startswith("%sa_fc_matmul")]
    assert len(conv) == 6 * 5 and len(fc) == 6 * 3   # 6 waves x layers
    assert trace.family_ns(events, CONV, lo, hi) == sum(
        e.end_ns - e.start_ns for e in conv)
    assert trace.family_ns(events, FC, lo, hi) == sum(
        e.end_ns - e.start_ns for e in fc)
    # an operation that only reads a kernel's output is not the kernel
    assert any("%sa_conv_implicit" in e.name and not e.name.startswith(
        "%sa_conv_implicit") for e in events)


def test_idle_gaps_and_busy_add_up_to_the_window(events):
    lo, hi = trace.window(events)
    gaps = dict(trace.idle_by_span(events, lo, hi))
    busy = trace.busy_ns(events, "/device:TPU:0", lo, hi)
    assert set(gaps) <= {"conv_dispatch", "fc_dispatch", "sync",
                         "scheduler", "generator"}
    assert sum(gaps.values()) * 1e9 + busy == pytest.approx(hi - lo, abs=2)


def test_top_ops_are_ranked_with_short_names(events):
    lo, hi = trace.window(events)
    top = trace.top_ops(events, lo, hi, n=5)
    assert len(top) == 5
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert top[0][0].startswith("%sa_conv_implicit.1 = f32[8,")
    assert top[0][0].endswith(" custom-call")


def test_interval_helpers():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace._complement([(2, 3), (5, 9)], 0, 8) == [(0, 2), (3, 5)]
    assert trace._intersect([(0, 4), (6, 9)], [(3, 7)]) == [(3, 4), (6, 7)]
    assert trace._subtract([(0, 10)], [(2, 3), (5, 6)]) == [
        (0, 2), (3, 5), (6, 10)]
    assert trace._subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
