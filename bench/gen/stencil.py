"""Node-stencil CSR built in row order.

A grid of ``nx × ny × nz`` nodes carries ``dofs`` unknowns each; the row of
unknown ``a`` of node ``i`` couples to every unknown of every node within
one step of ``i`` in each direction (27 nodes inside the grid).  Rows are
numbered node-major, ``3·i + a`` for three unknowns, so lexicographic node
offsets give ascending columns and the CSR needs no sort: one masked
compression of an ``(n, 27·dofs)`` column table.  The values are filled in
blocks of rows (:func:`blocks`), so that host memory stays bounded.
"""

from __future__ import annotations

import numpy as np

_ROWS_PER_BLOCK = 1 << 18


def pattern(grid, dofs: int):
    """``(n, indptr, indices)`` of the 27-node stencil with ``dofs`` unknowns
    per node on ``grid = (nx, ny, nz)``."""
    nx, ny, nz = (int(g) for g in grid)
    n_nodes = nx * ny * nz
    ix, iy, iz = np.unravel_index(np.arange(n_nodes), (nx, ny, nz))
    offs = [(dx, dy, dz) for dx in (-1, 0, 1) for dy in (-1, 0, 1)
            for dz in (-1, 0, 1)]
    ok = np.empty((n_nodes, len(offs)), bool)
    ncol = np.empty((n_nodes, len(offs)), np.int64)
    for k, (dx, dy, dz) in enumerate(offs):
        ok[:, k] = ((ix + dx >= 0) & (ix + dx < nx) & (iy + dy >= 0)
                    & (iy + dy < ny) & (iz + dz >= 0) & (iz + dz < nz))
        ncol[:, k] = np.arange(n_nodes) + (dx * ny + dy) * nz + dz
    # row (i, a) -> columns (j, b) for each live neighbour j, b = 0..dofs-1
    cols = (ncol[:, None, :, None] * dofs
            + np.arange(dofs)[None, None, None, :])
    cols = np.broadcast_to(cols, (n_nodes, dofs, len(offs), dofs))
    live = np.broadcast_to(ok[:, None, :, None], cols.shape)
    n = n_nodes * dofs
    cols = cols.reshape(n, -1)
    live = live.reshape(n, -1)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(live.sum(axis=1), out=indptr[1:])
    indices = cols[live].astype(np.int32)
    return n, indptr, indices


def blocks(n: int, indptr: np.ndarray, indices: np.ndarray):
    """``(lo, hi, rows, cols)`` for consecutive blocks of rows: the entries
    ``lo:hi`` of the CSR with the row and column of each, as ``int64``."""
    for r0 in range(0, n, _ROWS_PER_BLOCK):
        r1 = min(n, r0 + _ROWS_PER_BLOCK)
        lo, hi = int(indptr[r0]), int(indptr[r1])
        rows = np.repeat(np.arange(r0, r1, dtype=np.int64),
                         np.diff(indptr[r0:r1 + 1]))
        yield lo, hi, rows, indices[lo:hi].astype(np.int64)
