"""Per-layer metrics read from the program's counters.

``repro.serve.telemetry.counts()`` keys each counter by its name and by the
innermost program span it was counted in; the readers here add a
counter's totals over every span.  They are process totals, warm-up
included.  A program without the counters reads ``None``.
"""
from __future__ import annotations


def program_counts() -> dict | None:
    """The program's counters, ``None`` where it keeps none."""
    try:
        from repro.serve import telemetry
    except ImportError:
        return None
    return telemetry.counts()


def share(numerator: str, denominator: str,
          counts: dict | None = None) -> float | None:
    """100 x the total of counter ``numerator`` over the total of counter
    ``denominator``; ``None`` where the denominator was never counted.
    ``counts`` stands in for the program's ``telemetry.counts()``."""
    c = program_counts() if counts is None else counts
    if not c:
        return None
    total = {numerator: 0, denominator: 0}
    for (name, _), n in c.items():
        if name in total:
            total[name] += n
    if not total[denominator]:
        return None
    return 100.0 * total[numerator] / total[denominator]
