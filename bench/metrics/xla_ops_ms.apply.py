"""Device ms per apply of every op that is neither Pallas kernel: the XLA
ER stage and the permutation gathers."""

from bench.metrics import device


def read(rec):
    return device.other_ms(rec, "ops")
