"""Host ms per wave in the zoo's modeled-time plan (zoo.schedule spans), bulk cells."""
from bench.program_spans import host_ms_per_wave


def read(run):
    return host_ms_per_wave(run, "zoo.schedule")
