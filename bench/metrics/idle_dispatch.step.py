"""Share of a training cell's traced window in which no op ran on the chip
while the host was inside the loop's ``dispatch`` span (host loop layer):
the jitted step's call, from its entry until it returns."""
from bench.lib.idle import idle_under_span_pct


def read(traced):
    return idle_under_span_pct(traced, "dispatch")
