"""EHYB format construction invariants (paper Algorithms 1–2, §3.2–3.4)."""

import numpy as np
import pytest

from repro.core import SUITE, build_buckets, build_ehyb, poisson3d, powerlaw


def reconstruct_dense(e):
    """Invert the EHYB layout back to a dense matrix in original order."""
    n, V = e.n, e.vec_size
    d = np.zeros((e.n_pad, e.n_pad))
    for p in range(e.n_parts):
        base = p * V
        for i in range(V):
            r = base + i
            for k in range(e.ell_width):
                v = e.ell_vals[p, i, k]
                if v != 0.0:
                    d[r, base + int(e.ell_cols[p, i, k])] += v
    for s in range(e.er_rows):
        r = int(e.er_row_idx[s])
        for k in range(e.er_width):
            v = e.er_vals[s, k]
            if v != 0.0:
                d[r, int(e.er_cols[s, k])] += v
    # un-permute rows and columns
    out = np.zeros((n, n))
    rows = e.perm[: e.n_pad]
    for new_r in range(e.n_pad):
        old_r = rows[new_r]
        if old_r < n:
            for new_c in np.flatnonzero(d[new_r]):
                old_c = e.perm[new_c]
                if old_c < n:
                    out[old_r, old_c] += d[new_r, new_c]
    return out


@pytest.mark.parametrize("gen", [lambda: poisson3d(6),
                                 lambda: powerlaw(256, 6)])
def test_roundtrip_dense(gen):
    m = gen()
    e = build_ehyb(m, n_parts=4, vec_size=-(-m.n // 4 // 8) * 8)
    assert np.allclose(reconstruct_dense(e), m.to_dense())


def test_entry_conservation_and_bounds():
    m = poisson3d(8)
    e = build_ehyb(m)
    nnz_ell = int((e.ell_vals != 0).sum())
    nnz_er = int((e.er_vals != 0).sum())
    # structural zeros in the input could undercount; entries ≥ stored nnz
    assert nnz_ell + nnz_er <= m.nnz
    assert e.nnz_in + (m.nnz - e.nnz_in) == m.nnz
    assert e.vec_size <= 1 << 16              # uint16 local index (§3.4)
    assert e.ell_cols.dtype == np.uint16
    assert (e.ell_cols < e.vec_size).all()
    # rows sorted by in-partition length inside each partition (Algo 1 l.17)
    widths = (e.ell_vals != 0).sum(axis=2)
    for p in range(e.n_parts):
        w = widths[p]
        assert (np.diff(w) <= 0).all() or w.max() == 0 or True
        # non-increasing after sort (ties by orig index keep order)
        assert all(w[i] >= w[i + 1] for i in range(len(w) - 1))


def test_max_width_spills_to_er():
    m = powerlaw(512, 8)
    e_full = build_ehyb(m, n_parts=4, vec_size=128)
    e_cap = build_ehyb(m, n_parts=4, vec_size=128, max_width=8)
    assert e_cap.ell_width <= 8
    assert e_cap.nnz_in <= e_full.nnz_in
    # same matrix content (checked via SpMV in test_spmv_formats)


def test_bytes_model_orderings():
    m = poisson3d(8)
    e = build_ehyb(m)
    f32 = e.bytes_moved(4)
    f64 = e.bytes_moved(8)
    assert f64["total"] > f32["total"]
    assert e.bytes_moved(4)["total"] <= e.bytes_moved(4, col_bytes=4)["total"]
    # the packed staircase stores whole (Sb, 128) tiles: it sits between the
    # sliced and the uniform-tile layouts once a partition spans whole
    # tiles (V = 256 = one (2, 128) tile here)
    e = build_ehyb(m, n_parts=2, vec_size=256)
    sliced = e.bytes_moved(4, layout="sliced")["total"]
    tile = e.bytes_moved(4, layout="tile")["total"]
    packed = e.bytes_moved(4, layout="packed")["total"]
    assert sliced <= packed <= tile


def test_buckets_cover_all_partitions():
    m = poisson3d(8)
    e = build_ehyb(m)
    b = build_buckets(e, n_buckets=3)
    ids = np.concatenate(b.part_ids)
    assert sorted(ids.tolist()) == list(range(e.n_parts))
    for pid, w in zip(b.part_ids, b.widths):
        assert (e.part_widths[pid] <= w).all()
