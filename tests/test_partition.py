"""Partitioner invariants (paper §3.1 + Eq. 1–2)."""

import numpy as np
import pytest

from repro.core import (SUITE, choose_vec_size, make_partition, poisson3d,
                        unstructured)
from repro.core.partition import MAX_LANE_ROWS, lane_geometry


@pytest.mark.parametrize("method", ["natural", "bfs", "mincut", "hub"])
@pytest.mark.parametrize("gen", [lambda: poisson3d(8),
                                 lambda: unstructured(1024, 10)])
def test_partition_invariants(method, gen):
    m = gen()
    p = make_partition(m, method=method, n_parts=8,
                       vec_size=-(-m.n // 8 // 8) * 8 + 8)
    # every vertex in exactly one partition, capacity respected
    counts = np.bincount(p.part_vec, minlength=p.n_parts)
    assert counts.sum() == m.n
    assert counts.max() <= p.vec_size
    # perm/inv_perm are inverse bijections over the padded index space
    assert np.array_equal(p.perm[p.inv_perm], np.arange(p.n_pad))
    assert np.array_equal(p.inv_perm[p.perm], np.arange(p.n_pad))
    # partition-major layout: slot // vec_size == partition of the vertex
    real = p.perm < m.n
    slots = np.flatnonzero(real)
    assert np.array_equal(slots // p.vec_size,
                          p.part_vec[p.perm[real]])


def test_bfs_beats_random_locality():
    """Graph growing must exploit FEM locality: in-partition fraction far
    above the 1/P expectation of a random assignment."""
    m = poisson3d(12)
    p = make_partition(m, method="bfs", n_parts=8,
                       vec_size=-(-m.n // 8 // 8) * 8 + 8)
    frac = p.in_partition_fraction(m)
    assert frac > 0.5, frac            # random would be ~1/8


def test_choose_vec_size_eq12():
    """Paper Eq. 1–2: smallest K with dim·τ/(K·P) under the cache budget,
    the x block counted lane-padded and its lane-rows capped by the
    in-vreg gather."""
    n = 1_000_000
    n_parts, vec = choose_vec_size(n, dtype_bytes=4,
                                   vmem_budget_bytes=1 << 20, p_units=8)
    s, _ = lane_geometry(vec)
    assert s * 128 * 4 < (1 << 20)      # lane-padded VMEM bytes
    assert s <= MAX_LANE_ROWS           # gather span of the TPU kernel
    assert vec < (1 << 16)              # uint16 local indices (paper §3.4)
    assert vec % 8 == 0                 # sublane aligned
    assert n_parts % 8 == 0
    # minimality: one fewer K would violate a bound
    k = n_parts // 8
    if k > 1:
        prev_vec = -(-(-(-n // ((k - 1) * 8))) // 8) * 8
        prev_s, _ = lane_geometry(prev_vec)
        assert (prev_s * 128 * 4 >= (1 << 20) or prev_vec >= (1 << 16)
                or prev_s > MAX_LANE_ROWS)
    # HPCG's reference local grid (104³) lands on 552 partitions of 2040
    assert choose_vec_size(104 ** 3) == (552, 2040)


def test_natural_on_stencil_is_near_perfect():
    m = poisson3d(16)
    p = make_partition(m, method="natural", n_parts=8, vec_size=512)
    assert p.in_partition_fraction(m) > 0.85
