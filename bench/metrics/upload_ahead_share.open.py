"""Wave executor: percent of the waves' image uploads issued inside the
wave before them (program counters ``cnn.upload_ahead`` over
``cnn.uploads``), open-loop cells, where a ``serve()`` call seldom holds
two waves."""
from bench.counters import share


def read(run, counts=None):
    """``counts`` stands in for the program's ``telemetry.counts()``."""
    return share("cnn.upload_ahead", "cnn.uploads", counts)
