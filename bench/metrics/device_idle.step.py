"""Share of the traced window of a training cell in which no op ran on the chip
(device layer)."""
from bench.lib.readers import device_idle_pct


def read(traced):
    return device_idle_pct(traced)
