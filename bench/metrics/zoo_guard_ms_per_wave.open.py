"""Host ms per wave in the zoo's per-row isfinite guard (zoo.guard spans), open-loop cells."""
from bench.program_spans import host_ms_per_wave


def read(run):
    return host_ms_per_wave(run, "zoo.guard")
