"""HPCG's 27-point operator: one unknown per grid node, ``diagonal`` on the
main diagonal and ``offdiagonal`` on every other entry of the stencil.
Keys read from the configuration: ``grid``, ``diagonal``,
``offdiagonal``."""

from __future__ import annotations

import numpy as np

from bench.gen import stencil


def generate(cfg: dict) -> dict:
    n, indptr, indices = stencil.pattern(cfg["grid"], 1)
    diag, off = float(cfg["diagonal"]), float(cfg["offdiagonal"])
    data = np.empty(len(indices), np.float64)
    for lo, hi, rows, cols in stencil.blocks(n, indptr, indices):
        data[lo:hi] = np.where(rows == cols, diag, off)
    return {"n": n, "indptr": indptr, "indices": indices, "data": data}
