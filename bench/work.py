"""The work an SpMV needs, from the matrix alone, and the chip's peaks.

The counts read only ``n``, ``nnz``, the rhs width ``k`` and the value
dtype, never a format's tables: every implementation of the same product is
held to the same work, so a roofline share computed from them is a lower
bound that no layout change can inflate.  Index bytes are left out on
purpose (any format needs some, and how many is the format's business).
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
VECTOR_BYTES = 4            # x and y are float32 in every cell


def spmv_bytes(n: int, nnz: int, k: int, dtype) -> int:
    """HBM bytes of ``Y = A @ X`` with ``X``, ``Y`` of shape ``(n, k)``:
    every value read once, ``X`` read once, ``Y`` written once."""
    return (int(nnz) * np.dtype(dtype).itemsize
            + 2 * int(n) * int(k) * VECTOR_BYTES)


def spmv_flops(nnz: int, k: int) -> int:
    """One multiply and one add per stored entry and rhs column."""
    return 2 * int(nnz) * int(k)


def peak(device_kind: str, path=PEAKS) -> dict:
    """The peaks of ``device_kind`` (as JAX names it); an unknown kind is an
    error, never a default."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def spmv_min_seconds(n: int, nnz: int, k: int, dtype,
                     device_kind: str) -> float:
    """The least time the chip could take: the larger of bytes over peak
    HBM bandwidth and operations over peak FLOP/s (bytes bound an SpMV)."""
    pk = peak(device_kind)
    return max(spmv_bytes(n, nnz, k, dtype) / pk["hbm_bytes_per_s"],
               spmv_flops(nnz, k) / pk["bf16_flops_per_s"])
