"""The ER window: each partition's ER entries served from a cached window of
x by the Pallas kernel (interpreted on the CPU), the rest by XLA.

One parametrised test runs every pattern kind through the whole packed apply
at k = 1 and k = 3 against the float64 ``scipy.sparse`` product, and checks
that each ER entry lands exactly once, in the window or in the leftover:

* a 27-point stencil (on partitions of the benchmark's 2040 rows, so a
  column spans two tiles) and a 3-dof Q1 elasticity pattern on ``natural``
  partitions, whose ER all fits in the window;
* a power-law pattern with the window capped at 3 lane-rows, and the
  stencil with its window tiles capped at four a partition, which split
  their ER between window and leftover;
* a block-diagonal matrix whose blocks are the partitions: no ER at all.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import repro.core.ehyb as ehyb_mod
from repro.analysis import verify
from repro.core import (EHYBDevice, EHYBPackedDevice, build_ehyb,
                        elasticity3d,
                        pack_er_window, pack_staircase, poisson3d27,
                        powerlaw)
from repro.core.matrices import from_coo
from repro.core.partition import LANES, lane_geometry
from repro.core.spmv import ehyb_spmv
from repro.kernels import ehyb_spmv_packed_pallas


def _block_diagonal(n_blocks: int = 4, size: int = 64, seed: int = 0):
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for b in range(n_blocks):
        r, c = np.nonzero(rng.random((size, size)) < 0.1)
        rows.append(b * size + r)
        cols.append(b * size + c)
    n = n_blocks * size
    rows = np.concatenate(rows + [np.arange(n)])
    cols = np.concatenate(cols + [np.arange(n)])
    vals = rng.standard_normal(rows.size) + 8.0 * (rows == cols)
    return from_coo(n, rows, cols, vals, sum_duplicates=True)


CASES = {
    # name: (EHYB and its matrix, window cap, ER_WINDOW_TILE_BYTES)
    # the benchmark's geometry: 2040-row partitions, two tiles a column
    "stencil27": lambda: (build_ehyb(m := poisson3d27(20), method="natural",
                                     n_parts=4, vec_size=2040), m, None,
                          None),
    "elasticity": lambda: (build_ehyb(m := elasticity3d(5),
                                      method="natural"), m, None, None),
    "split": lambda: (build_ehyb(m := powerlaw(600, 6)), m, 3, None),
    # four tiles a partition: the stencil's later ER columns go to XLA
    "tile_bound": lambda: (build_ehyb(m := poisson3d27(20), method="natural",
                                      n_parts=4, vec_size=2040), m, None,
                           4 * 8 * LANES * 6),
    "er_free": lambda: (build_ehyb(m := _block_diagonal(), method="natural",
                                   n_parts=4, vec_size=64), m, None, None),
}


def _served(e, w):
    """(entry id, global row, global col) of every entry the window and the
    leftover tables serve, decoded from their own layouts; entry ids are
    flat indices into the ER tables, planted as values."""
    ids = np.arange(1, e.er_vals.size + 1, dtype=np.float64)
    marked = w.refill(ids.reshape(e.er_vals.shape))
    v_, (_, sb) = e.vec_size, lane_geometry(e.vec_size)
    out = []
    p, tau, sub, lane = np.nonzero(marked.vals)
    k = np.array([np.searchsorted(w.col_starts[q], t, side="right") - 1
                  for q, t in zip(p, tau)], dtype=np.int64)
    row = (tau - w.col_starts[p, k]) * sb * LANES + sub * LANES + lane
    c = w.cols[p, tau, sub, lane].astype(np.int64)
    col = w.win_rows[p, c // LANES].astype(np.int64) * LANES + c % LANES
    out.append(np.stack([marked.vals[p, tau, sub, lane] - 1,
                         p * v_ + row, col], axis=1))
    if marked.left is not None:
        g = marked.left
        p, s, j = np.nonzero(g["er_p_vals"])
        out.append(np.stack([g["er_p_vals"][p, s, j] - 1,
                             p * v_ + g["er_p_rows"][p, s],
                             g["er_p_cols"][p, s, j]], axis=1))
    return np.concatenate(out).astype(np.int64) if out else \
        np.empty((0, 3), np.int64)


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("case", sorted(CASES))
def test_er_window_apply_and_cover(case, k, rng, monkeypatch):
    e, m, cap, tile_bytes = CASES[case]()
    if tile_bytes is not None:
        monkeypatch.setattr(ehyb_mod, "ER_WINDOW_TILE_BYTES", tile_bytes)
    w = (pack_er_window(e) if cap is None
         else pack_er_window(e, max_lane_rows=cap))
    if case in ("split", "tile_bound"):
        assert w.entries and w.leftover
    elif case == "er_free":
        assert w.entries == 0 and w.leftover == 0
    else:
        assert w.entries and w.leftover == 0
        assert w.lane_rows % 8 == 0 and w.lane_rows <= 512
    if tile_bytes is not None:
        assert w.col_starts[:, -1].max() <= 4

    # each ER entry exactly once, at its own row and column
    live = np.asarray(e.fill_plan["er_dst"], np.int64)
    got = _served(e, w)
    assert got.shape[0] == live.size == w.entries + w.leftover
    assert np.array_equal(np.sort(got[:, 0]), np.sort(live))
    we = e.er_width
    np.testing.assert_array_equal(got[:, 1], e.er_row_idx[got[:, 0] // we])
    np.testing.assert_array_equal(got[:, 2], e.er_cols.reshape(-1)[got[:, 0]])

    dev = EHYBPackedDevice.from_packed(pack_staircase(e), window=w)
    assert (dev.win_vals is None) == (w.entries == 0)
    assert (dev.er_p_vals is None) == (w.leftover == 0)
    assert verify(dev) == []
    shape = (m.n,) if k == 1 else (m.n, k)
    x = rng.standard_normal(shape)
    y = np.asarray(ehyb_spmv_packed_pallas(
        dev, jnp.asarray(x, jnp.float32), interpret=True), np.float64)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=(m.n, m.n))
    y_ref = a @ x
    scale = np.abs(y_ref).max()
    assert np.abs(y - y_ref).max() <= 1e-5 * scale


# ---------------------------------------------------------------------------
# the window's invariants catch each kind of fault
# ---------------------------------------------------------------------------

def _window_pair():
    e = build_ehyb(powerlaw(600, 6))
    pk = pack_staircase(e)
    w = pack_er_window(e, max_lane_rows=3)
    return e, pk, w


def _plant(kind, w, e):
    """A copy of ``w`` with one fault of ``kind``."""
    if kind == "window-column":
        cols = w.cols.copy()
        dst = w.entry_slots()[1][0]
        cols.reshape(-1)[dst] = w.lane_rows * LANES       # past the window
        return dataclasses.replace(w, cols=cols), "index-bound.er-window"
    if kind == "window-row":
        rows = w.win_rows.copy()
        rows[0, 0] = -(-e.n_pad // LANES)                # past x
        return dataclasses.replace(w, win_rows=rows), "index-bound.er-window"
    # one entry served twice: a leftover entry marked as a window entry too
    # (host: the window's mask; device: the values it uploads)
    mask = w.plan["mask"].copy()
    mask.reshape(-1)[w.left_entries()[0]] = True
    bad = dataclasses.replace(w, plan={**w.plan, "mask": mask})
    return bad.refill(e.er_vals), "er-window-cover"


@pytest.mark.parametrize("where", ["host", "device"])
@pytest.mark.parametrize("kind", ["window-column", "window-row",
                                  "served-twice"])
def test_er_window_invariants_catch_planted_faults(kind, where):
    e, pk, w = _window_pair()
    assert verify(pk) == []
    bad, rule = _plant(kind, w, e)
    if where == "host":
        e._er_window = bad
        rules = {f.rule for f in verify(pk)}
    else:
        rules = {f.rule for f in verify(
            EHYBPackedDevice.from_packed(pk, window=bad))}
    assert rule in rules, (kind, where, rules)


def test_window_height_is_chosen_from_the_pattern():
    """A scattered pattern splits by the cost model alone, under the uint16
    cap: the window keeps the lane-rows that hold most entries, and the
    rest stay in XLA."""
    e = build_ehyb(powerlaw(20000, 8), method="bfs")
    w = pack_er_window(e)
    assert w.entries and w.leftover
    assert w.lane_rows < -(-e.n_pad // LANES)
    assert verify(pack_staircase(e)) == []


def test_er_window_without_fill_plan_skips_stored_zeros(rng):
    """Without a fill plan the live ER entries are the nonzero values; a
    zero inside a row leaves a hole in its window column, and the apply
    still matches the XLA ER path."""
    base = build_ehyb(poisson3d27(12), method="natural", vec_size=512)
    er = base.er_vals.copy()
    wide = np.flatnonzero((er != 0).sum(axis=1) >= 3)
    er[wide, 1] = 0.0
    e = dataclasses.replace(base, er_vals=er, fill_plan=None)
    w = pack_er_window(e)
    assert w.entries == np.count_nonzero(er) and w.leftover == 0
    x = rng.standard_normal((e.n, 3))
    y = ehyb_spmv_packed_pallas(
        EHYBPackedDevice.from_packed(pack_staircase(e), window=w),
        jnp.asarray(x, jnp.float32), interpret=True)
    y_ref = np.asarray(ehyb_spmv(EHYBDevice.from_ehyb(e, dtype=jnp.float32),
                                 jnp.asarray(x, jnp.float32)), np.float64)
    np.testing.assert_allclose(np.asarray(y, np.float64), y_ref,
                               rtol=0, atol=1e-5 * np.abs(y_ref).max())
