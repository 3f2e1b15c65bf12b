"""The readers of the program's own spans (``bench/program_spans.py``), on a
hand-built run: warm-up, an untraced half of two waves and a traced half
of two waves whose ``serve()`` calls sit on the trace's clock at offsets
100 us apart."""
import pytest

from bench import program_spans, readers, system, trace
from repro.serve.telemetry import Span

DEVICE = "/device:TPU:0"
HOST = "/host:CPU"
LO, MID, HI = 1_000_000, 5_000_000, 9_000_000     # the window, host clock
OC = 10_000_000_000                               # trace - host, call C
OD = OC + 100_000                                 # call D: 100 us of drift


class Source:
    """Stands in for the program's recorder: hands back ``recs`` whatever
    window is asked for, so the readers' own filter is what is tested."""

    def __init__(self, recs):
        self.recs = recs

    def records(self, lo_ns, hi_ns):
        return self.recs


def _wave(recs, t, call, wave, upload, wait, plan, guard):
    """One serve() call of one wave from host time ``t``: spans at fixed
    places, with the given durations (ns) of the measured ones."""
    def add(name, s, e, parent, ident):
        recs.append(Span(len(recs), name, t + s, t + e, parent, ident))
        return recs[-1].seq

    root = add("zoo.serve", 10_000, 990_000, -1, call)
    add("zoo.schedule", 20_000, 20_000 + plan, root, call)
    ex = add("zoo.execute", 200_000, 980_000, root, call)
    w = add("cnn.wave", 210_000, 900_000, ex, wave)
    add("cnn.upload", 220_000, 220_000 + upload, w, wave)
    add("cnn.conv_dispatch", 300_000, 400_000, w, wave)
    add("cnn.fc_dispatch", 400_000, 450_000, w, wave)
    add("cnn.logits_wait", 500_000, 500_000 + wait, w, wave)
    add("zoo.guard", 910_000, 910_000 + guard, ex, call)
    add("zoo.account", 985_000, 989_000, root, call)


@pytest.fixture
def recs():
    out: list = []
    _wave(out, 0, 0, 0, 999_000, 1, 999_000, 1)           # warm-up
    _wave(out, 1_100_000, 1, 1, 50_000, 280_000, 100_000, 80_000)
    _wave(out, 2_600_000, 2, 2, 70_000, 300_000, 140_000, 60_000)
    _wave(out, 5_500_000, 3, 3, 1, 390_000, 1, 1)          # traced: C
    _wave(out, 7_000_000, 4, 4, 1, 390_000, 1, 1)          # traced: D
    return out


@pytest.fixture
def run():
    spans = system.Spans()
    spans.records = [("window", LO, HI)] + [
        ("serve", t, t + 1_000_000)
        for t in (0, 1_100_000, 2_600_000, 5_500_000, 7_000_000)]
    ev = [trace.Event(HOST, "python", "bench/serve", t + off,
                      t + off + 1_000_000)
          for t, off in ((5_500_000, OC), (7_000_000, OD))]
    # a device operation inside each traced logits wait: call C's wholly,
    # call D's overlapping the wait's first 50 us only
    ev += [trace.Event(DEVICE, "XLA Ops", "%op", OC + a, OC + b)
           for a, b in ((5_600_000, 5_700_000), (6_100_000, 6_200_000))]
    ev += [trace.Event(DEVICE, "XLA Ops", "%op", OD + a, OD + b)
           for a, b in ((7_100_000, 7_300_000), (7_450_000, 7_550_000))]
    return readers.Run(cfg={}, peak={}, chips=1, spans=spans,
                       host_lo_ns=LO, host_hi_ns=MID, host_served=4,
                       host_decisions=[(2, False), (2, False)],
                       events=ev, trace_lo=OC + MID, trace_hi=OC + HI,
                       planes=[DEVICE])


def test_host_window_keeps_the_untraced_half_only(run, recs):
    got = program_spans.program_records(run.host_lo_ns, run.host_hi_ns,
                                        Source(recs))
    assert {r.ident for r in got if r.name == "zoo.serve"} == {1, 2}
    assert all(LO <= r.start_ns and r.end_ns <= MID for r in got)


@pytest.mark.parametrize("name, ms", [
    ("zoo.schedule", (100_000 + 140_000) / 2 / 1e6),
    ("zoo.guard", (80_000 + 60_000) / 2 / 1e6),
    ("cnn.upload", (50_000 + 70_000) / 2 / 1e6),
    ("cnn.logits_wait", (280_000 + 300_000) / 2 / 1e6),
])
def test_host_time_per_wave(run, recs, name, ms):
    got = program_spans.host_ms_per_wave(run, name, Source(recs))
    assert got == pytest.approx(ms, rel=1e-12)


def test_spans_move_to_the_trace_clock_by_their_own_call(run, recs):
    traced = program_spans.program_records(MID, HI, Source(recs))
    mapped = program_spans.on_trace_clock(run, traced)
    assert len(mapped) == len(traced) == 20
    for r, s, e in mapped:
        off = OC if r.ident == 3 else OD
        assert (s, e) == (r.start_ns + off, r.end_ns + off)


def test_idle_split_adds_up_to_the_idle_share(run, recs):
    split = program_spans.idle_by_program_span(run, Source(recs))
    assert sum(split.values()) == pytest.approx(
        readers.device_idle_share(run), abs=1e-9)
    # C's wait is 390 us with 100 us busy, D's 390 us with 50 us busy
    want = 100.0 * (290_000 + 340_000) / (HI - MID)
    assert split["cnn.logits_wait"] == pytest.approx(want, rel=1e-12)
    assert program_spans.idle_share_in(run, "cnn.logits_wait",
                                       Source(recs)) == split[
                                           "cnn.logits_wait"]
    # every operation lies inside a zoo.serve: the idle time outside the
    # program's spans is the half less the two calls' roots
    assert split[program_spans.OUTSIDE] == pytest.approx(
        100.0 * (HI - MID - 2 * 980_000) / (HI - MID), rel=1e-12)


@pytest.mark.parametrize("source", [None, [], "no waves"],
                         ids=["wrapped", "empty", "no_waves"])
def test_nothing_to_read_reads_none(run, recs, source):
    if source == "no waves":
        source = [r for r in recs if r.name != "cnn.wave"]
    src = Source(source)
    assert program_spans.host_ms_per_wave(run, "cnn.upload", src) is None
    if source is None or source == []:
        assert program_spans.idle_by_program_span(run, src) is None


def test_a_program_without_the_recorder_reads_none(run, monkeypatch):
    monkeypatch.setattr(program_spans, "_recorder", lambda: None)
    assert program_spans.host_ms_per_wave(run, "zoo.guard") is None
    assert program_spans.idle_share_in(run, "cnn.logits_wait") is None


def test_unpaired_serve_calls_read_none(run, recs):
    run.events = [e for e in run.events if e.start_ns != 7_000_000 + OD]
    assert program_spans.idle_by_program_span(run, Source(recs)) is None
