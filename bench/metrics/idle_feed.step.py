"""Share of a training cell's traced window in which no op ran on the chip
while the host was inside the loop's ``feed`` span (host loop layer):
the next batch and the fold of the round's key."""
from bench.lib.idle import idle_under_span_pct


def read(traced):
    return idle_under_span_pct(traced, "feed")
