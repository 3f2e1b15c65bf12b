"""Percent of the traced half idle in cnn.logits_wait (innermost span), open-loop cells."""
from bench.program_spans import idle_share_in


def read(run):
    return idle_share_in(run, "cnn.logits_wait")
