"""Share of a training cell's traced window in which no op ran on the chip
while the host was inside the loop's ``log`` span (host loop layer):
the tracker's log of the round's metrics, with their device-to-host reads."""
from bench.lib.idle import idle_under_span_pct


def read(traced):
    return idle_under_span_pct(traced, "log")
