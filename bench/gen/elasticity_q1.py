"""Linear elasticity on a uniform hexahedral mesh of the unit cube with
trilinear (Q1) elements: three displacement unknowns per node, dense 3×3
blocks between every pair of nodes that share an element.

Each element's 24×24 stiffness matrix is integrated by 2×2×2 Gauss
quadrature, exact for Q1 on a cube, from Young's modulus and Poisson's
ratio.  The assembled value of an entry depends only on the offset between
its two nodes and on which elements around the row's node exist, that is on
whether the node lies on the low face, inside or on the high face along each
axis: a table of 27 such classes × 27 offsets × 3 × 3 fills the CSR.  The
nodes of the Dirichlet face keep their diagonal entry; every other entry of
their rows and columns is 0, stored, so that the pattern stays the mesh's.

Keys read from the configuration: ``node_grid``, ``youngs_modulus``,
``poisson_ratio``, ``dirichlet_face`` (``"y=0"``).
"""

from __future__ import annotations

import itertools

import numpy as np

from bench.gen import stencil

_CORNERS = np.array(list(itertools.product((0, 1), repeat=3)))   # (8, 3)


def element_stiffness(h: float, young: float, nu: float) -> np.ndarray:
    """``K[l, p, m, q]``: the stiffness of a cube element of edge ``h``
    between unknown ``p`` of local node ``l`` and unknown ``q`` of local
    node ``m`` (local node ``l`` at corner ``_CORNERS[l]``)."""
    lam = young * nu / ((1 + nu) * (1 - 2 * nu))
    mu = young / (2 * (1 + nu))
    g = np.array([0.5 - 0.5 / np.sqrt(3), 0.5 + 0.5 / np.sqrt(3)])
    k = np.zeros((8, 3, 8, 3))
    for xi in itertools.product(g, repeat=3):
        xi = np.array(xi)
        # shape values per axis, and the derivative of each node's function
        f = np.where(_CORNERS == 1, xi, 1 - xi)                  # (8, 3)
        df = np.where(_CORNERS == 1, 1.0, -1.0)                  # (8, 3)
        grad = np.stack([df[:, a] * np.prod(np.delete(f, a, axis=1), axis=1)
                         for a in range(3)], axis=1) / h         # (8, 3)
        dots = grad @ grad.T                                     # (8, 8)
        k += 0.125 * h ** 3 * (
            lam * np.einsum("lp,mq->lpmq", grad, grad)
            + mu * np.einsum("lq,mp->lpmq", grad, grad)
            + mu * np.einsum("lm,pq->lpmq", dots, np.eye(3)))
    return k


def _table(ke: np.ndarray) -> np.ndarray:
    """``T[c, o, p, q]``: the assembled entry of a node of class ``c`` (per
    axis 0 on the low face, 1 inside, 2 on the high face) with the node at
    offset ``o`` (``(dx+1)·9 + (dy+1)·3 + (dz+1)``)."""
    t = np.zeros((27, 27, 3, 3))
    for c, cls in enumerate(itertools.product(range(3), repeat=3)):
        for lo, e in enumerate(_CORNERS):
            # the node is local corner e of the element below-left of it by
            # e; that element exists unless a face of the mesh cuts it off
            if any((cls[a] == 0 and e[a] == 1) or (cls[a] == 2 and e[a] == 0)
                   for a in range(3)):
                continue
            for m, f in enumerate(_CORNERS):
                o = int(np.dot(f - e + 1, (9, 3, 1)))
                t[c, o] += ke[lo, :, m, :]
    return t


def generate(cfg: dict) -> dict:
    if cfg["dirichlet_face"] != "y=0":
        raise ValueError(f"unknown Dirichlet face {cfg['dirichlet_face']!r}")
    grid = np.array([int(g) for g in cfg["node_grid"]])
    if len(set(grid.tolist())) != 1 or grid[0] < 3:
        raise ValueError("the mesh is a cube of three or more nodes a side")
    n, indptr, indices = stencil.pattern(grid, 3)
    table = _table(element_stiffness(1.0 / (grid[0] - 1),
                                     float(cfg["youngs_modulus"]),
                                     float(cfg["poisson_ratio"])))
    # per node: its class and whether it is fixed; per node offset: its
    # index o (a neighbour's offset is unique for three or more nodes a side)
    ijk = np.stack(np.unravel_index(np.arange(n // 3), grid), axis=1)
    node_class = np.where(ijk == 0, 0, np.where(ijk == grid - 1, 2, 1)) @ (
        9, 3, 1)
    fixed_node = ijk[:, 1] == 0
    reach = grid[1] * grid[2] + grid[2] + 1
    offset = np.zeros(2 * reach + 1, np.int64)
    for d in itertools.product((-1, 0, 1), repeat=3):
        offset[reach + (d[0] * grid[1] + d[1]) * grid[2] + d[2]] = np.dot(
            np.add(d, 1), (9, 3, 1))
    data = np.empty(len(indices), np.float64)
    for lo, hi, rows, cols in stencil.blocks(n, indptr, indices):
        rn, cn = rows // 3, cols // 3
        v = table[node_class[rn], offset[reach + cn - rn], rows % 3,
                  cols % 3]
        fixed = fixed_node[rn] | fixed_node[cn]
        data[lo:hi] = np.where(fixed & (rows != cols), 0.0, v)
    return {"n": n, "indptr": indptr, "indices": indices, "data": data}
