"""Device ms of the fused CG vector update (``fused_cg_update``) per CG
iteration."""

from bench.metrics import device


def read(rec):
    return device.kernel_ms(rec, "fused_cg_update", "iters")
