"""Host ms per wave waiting for the logits (cnn.logits_wait spans), open-loop cells."""
from bench.program_spans import host_ms_per_wave


def read(run):
    return host_ms_per_wave(run, "cnn.logits_wait")
