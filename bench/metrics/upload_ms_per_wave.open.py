"""Host ms per wave stacking and uploading the images (cnn.upload spans), open-loop cells."""
from bench.program_spans import host_ms_per_wave


def read(run):
    return host_ms_per_wave(run, "cnn.upload")
