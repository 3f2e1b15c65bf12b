"""Wave executor: percent of the waves' image uploads issued inside the
wave before them, under its device work (program counters
``cnn.upload_ahead`` over ``cnn.uploads``), bulk cells.  The first wave
of each ``serve()`` call cannot go ahead: 7 of 8 at most."""
from bench.counters import share


def read(run, counts=None):
    """``counts`` stands in for the program's ``telemetry.counts()``."""
    return share("cnn.upload_ahead", "cnn.uploads", counts)
