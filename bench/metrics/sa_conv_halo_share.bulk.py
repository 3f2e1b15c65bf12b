"""SA-CONV kernel: input bytes fetched a second time as row-band halos over
the compulsory input slab bytes, percent, bulk cells.

Reads the program's process-wide counters ``sa_conv.halo_bytes`` and
``sa_conv.slab_bytes`` (``repro.serve.telemetry``, counted per dispatched
conv inside ``cnn.conv_dispatch``).  A bulk mix cuts one wave shape, so
the ratio of the totals is the window's.  ``None`` where the program
keeps no such counters.
"""


def _counts():
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    return telemetry.counts()


def read(run, counts=None):
    """``counts`` stands in for the program's ``telemetry.counts()``."""
    c = _counts() if counts is None else counts
    if not c:
        return None
    total = {"sa_conv.slab_bytes": 0, "sa_conv.halo_bytes": 0}
    for (name, _), n in c.items():
        if name in total:
            total[name] += n
    if not total["sa_conv.slab_bytes"]:
        return None
    return 100.0 * total["sa_conv.halo_bytes"] / total["sa_conv.slab_bytes"]
