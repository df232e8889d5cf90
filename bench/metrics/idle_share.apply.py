"""Share (%) of the traced apply window in which no op ran on the device."""

from bench.metrics import device


def read(rec):
    return device.idle_pct(rec)
