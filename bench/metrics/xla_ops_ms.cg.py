"""Device ms per CG iteration of every op that is neither Pallas kernel:
the XLA ER stage, the loop's own ops."""

from bench.metrics import device


def read(rec):
    return device.other_ms(rec, "iters")
