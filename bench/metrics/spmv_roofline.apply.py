"""Share (%) of the chip's HBM roofline that one apply reaches: the
matrix's least bytes over peak bandwidth, over device busy time per apply."""

from bench.metrics import device


def read(rec):
    return device.spmv_roofline_pct(rec)
