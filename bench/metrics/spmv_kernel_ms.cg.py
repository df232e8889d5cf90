"""Device ms of the ``ehyb_packed_spmv`` Pallas kernel per CG iteration."""

from bench.metrics import device


def read(rec):
    return device.kernel_ms(rec, "ehyb_packed_spmv", "iters")
