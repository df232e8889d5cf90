"""Permuted-space execution + fused-ER megakernel conformance.

The once-per-solve permutation contract (core/solver.py DESIGN): running the
whole Krylov loop in the EHYB-reordered space must reproduce the
original-space trajectory (same iterate up to fp summation order), across
solvers × preconditioners × EHYB-family formats × dtypes.  The fused-ER
kernel (one pallas_call per SpMV) is swept against the dense oracle,
including the empty-ER (single partition) and ER-heavy power-law extremes.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import autotune as at
from repro.core import (EHYBDevice, build_ehyb, build_spmv, cg,
                        group_er_by_partition, poisson3d, powerlaw, solve,
                        spmv, unstructured)

EHYB_FAMILY = [f for f in at.available_formats() if f.startswith("ehyb")]


# ---------------------------------------------------------------------------
# operator space API
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", sorted(at.available_formats()))
def test_operator_space_support(fmt, rng):
    m = poisson3d(5)
    op = build_spmv(m, format=fmt)
    assert op.supports_permuted == fmt.startswith("ehyb")
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    if not op.supports_permuted:
        with pytest.raises(ValueError):
            op.to_permuted(x)
        return
    # round trip is the identity; permuted apply == original apply
    x_new = op.to_permuted(x)
    assert x_new.shape == (op.n_pad,)
    np.testing.assert_array_equal(np.asarray(op.from_permuted(x_new)),
                                  np.asarray(x))
    y1 = np.asarray(op(x))
    y2 = np.asarray(op.from_permuted(op.matvec_permuted(x_new)))
    np.testing.assert_allclose(y1, y2, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("fmt", sorted(EHYB_FAMILY))
def test_permuted_apply_batched(fmt, rng):
    m = unstructured(256, 8)
    op = build_spmv(m, format=fmt)
    xs = jnp.asarray(rng.standard_normal((m.n, 3)), jnp.float32)
    y_ref = m.to_dense() @ np.asarray(xs, np.float64)
    y = np.asarray(op.from_permuted(op.matvec_permuted(op.to_permuted(xs))),
                   np.float64)
    assert np.abs(y - y_ref).max() / max(np.abs(y_ref).max(), 1) < 1e-4


# ---------------------------------------------------------------------------
# solve equivalence: original vs permuted space
# ---------------------------------------------------------------------------

MATS = {
    "poisson": lambda: poisson3d(6),
    "unstruct": lambda: unstructured(512, 10, seed=9),
}


@pytest.mark.parametrize("fmt", sorted(EHYB_FAMILY))
@pytest.mark.parametrize("method", ["cg", "bicgstab"])
@pytest.mark.parametrize("pc", ["none", "jacobi", "spai"])
def test_solve_space_equivalence(fmt, method, pc, rng):
    """Same trajectory in both spaces: iterate matches to fp tolerance and
    iteration counts agree (summation order is the only difference)."""
    m = poisson3d(6)
    b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    kw = dict(method=method, precond=pc, format=fmt, tol=1e-6, max_iters=400)
    r_orig = solve(m, b, space="original", **kw)
    r_perm = solve(m, b, space="permuted", **kw)
    assert bool(r_orig.converged) and bool(r_perm.converged)
    assert abs(int(r_orig.iters) - int(r_perm.iters)) <= 1
    x1, x2 = np.asarray(r_orig.x, np.float64), np.asarray(r_perm.x, np.float64)
    scale = max(np.abs(x1).max(), 1e-30)
    assert np.abs(x1 - x2).max() / scale < 1e-3


@pytest.mark.parametrize("mat", sorted(MATS))
def test_solve_auto_space_is_permuted_for_ehyb(mat, rng):
    """space="auto" (the default) runs EHYB-family operators in the permuted
    space and still solves the system."""
    m = MATS[mat]()
    b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    method = "cg" if mat == "poisson" else "bicgstab"
    # (bicgstab on the power-law generator breaks down for every format and
    # space alike — matrix property, not an execution-space one; the ER-heavy
    # fused path is covered by the megakernel sweep below instead)
    r = solve(m, b, method=method, format="ehyb", precond="jacobi",
              tol=1e-5, max_iters=1500)
    assert bool(r.converged)
    ax = m.spmv(np.asarray(r.x, np.float64))
    rel = np.linalg.norm(ax - np.asarray(b)) / np.linalg.norm(np.asarray(b))
    assert rel < 1e-3


def test_solve_bf16_space_equivalence(rng):
    m = poisson3d(5)
    b = jnp.asarray(rng.standard_normal(m.n), jnp.bfloat16)
    kw = dict(method="cg", precond="jacobi", format="ehyb", tol=1e-2,
              max_iters=200)
    r_orig = solve(m, b, space="original", **kw)
    r_perm = solve(m, b, space="permuted", **kw)
    x1 = np.asarray(r_orig.x, np.float64)
    x2 = np.asarray(r_perm.x, np.float64)
    assert np.abs(x1 - x2).max() / max(np.abs(x1).max(), 1e-30) < 0.15


def test_solve_permuted_space_rejected_for_flat_formats(rng):
    m = poisson3d(5)
    b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    with pytest.raises(ValueError):
        solve(m, b, format="csr", space="permuted")


def test_fused_cg_update_matches_jnp(rng):
    """The fused Pallas CG-step kernel == the plain jnp update math."""
    from repro.kernels import fused_cg_update

    n = 1000
    x, r, p, ap = (jnp.asarray(rng.standard_normal(n), jnp.float32)
                   for _ in range(4))
    minv = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    alpha = jnp.float32(0.37)
    xn, rn, zn, rz, rr = fused_cg_update(x, r, p, ap, minv, alpha)
    rn_ref = r - alpha * ap
    zn_ref = minv * rn_ref
    np.testing.assert_allclose(np.asarray(xn), np.asarray(x + alpha * p),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(rn), np.asarray(rn_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(zn), np.asarray(zn_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(rz), float(jnp.vdot(rn_ref, zn_ref)),
                               rtol=1e-4)
    np.testing.assert_allclose(float(rr), float(jnp.vdot(rn_ref, rn_ref)),
                               rtol=1e-4)


def test_cg_fused_update_path_matches_plain(rng):
    """cg(fused_update=True) reproduces the plain body's trajectory."""
    m = poisson3d(5)
    op = build_spmv(m, format="ehyb")
    b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    from repro.core.solver import precond_inv_diag

    inv = jnp.asarray(precond_inv_diag(m, "jacobi"), jnp.float32)
    pre = lambda r: inv * r
    r1 = cg(op.matvec, b, pre, tol=1e-6, max_iters=200)
    r2 = cg(op.matvec, b, pre, tol=1e-6, max_iters=200,
            fused_update=True, precond_inv=inv)
    assert abs(int(r1.iters) - int(r2.iters)) <= 1
    np.testing.assert_allclose(np.asarray(r1.x), np.asarray(r2.x),
                               rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# fused-ER kernel conformance (empty-ER and ER-heavy extremes)
# ---------------------------------------------------------------------------

def _fused_cases():
    m_er = powerlaw(512, 8, seed=11)        # ER-heavy (power-law spills)
    m_un = unstructured(512, 10)
    m_one = unstructured(256, 8)            # single partition -> empty ER
    return [
        ("powerlaw", m_er, build_ehyb(m_er)),
        ("unstruct", m_un, build_ehyb(m_un)),
        ("one_part", m_one,
         build_ehyb(m_one, n_parts=1, vec_size=-(-m_one.n // 8) * 8)),
    ]


@pytest.mark.parametrize("case", range(3))
@pytest.mark.parametrize("dt,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 1e-1)])
def test_fused_megakernel_vs_dense_oracle(case, dt, tol, rng):
    from repro.core import EHYBPackedDevice, pack_staircase
    from repro.core.spmv import _to_permuted
    from repro.kernels import (ehyb_spmv_packed_pallas,
                               ehyb_spmv_packed_pallas_permuted)

    name, m, e = _fused_cases()[case]
    dev = EHYBPackedDevice.from_packed(pack_staircase(e), dtype=dt)
    if name == "one_part":
        assert not dev.has_er              # everything cached, ER fully empty
    if name == "powerlaw":
        # ER exercised, all of it from the ER window (x is 8 lane-rows)
        assert dev.has_er and np.asarray(dev.win_vals).any()
        assert dev.er_p_vals is None
    dense = m.to_dense()
    for shape in ((m.n,), (m.n, 2)):
        x = rng.standard_normal(shape)
        y_ref = dense @ x
        scale = max(np.abs(y_ref).max(), 1.0)
        xj = jnp.asarray(x, dtype=dt)
        y = np.asarray(ehyb_spmv_packed_pallas(dev, xj), np.float64)
        assert np.abs(y - y_ref).max() / scale < tol, (name, shape)
        # permuted-space entry: one pallas_call + XLA ER, no gathers
        x_new, _ = _to_permuted(dev, xj)
        y_new = ehyb_spmv_packed_pallas_permuted(dev, x_new)
        y_p = np.asarray(y_new[np.asarray(dev.inv_perm)[: m.n]], np.float64)
        y_p = y_p if len(shape) > 1 else y_p[:, 0]
        assert np.abs(y_p - y_ref).max() / scale < tol, (name, shape)


def test_er_grouping_is_a_partition_of_er_slots():
    """Every live ER slot lands in exactly its owning partition with the
    right local row; padding slots are value-zero."""
    m = powerlaw(512, 8, seed=11)
    e = build_ehyb(m)
    g = group_er_by_partition(e)
    v = e.vec_size
    live = np.flatnonzero((e.er_vals != 0).any(axis=1))
    assert g["has_er"] and g["n_er_live"] == len(live)
    # reconstruct (global row, col, val) triples from the grouped tiles and
    # compare against the flat ER tables
    flat = set()
    for s in live:
        r = int(e.er_row_idx[s])
        for k in range(e.er_width):
            if e.er_vals[s, k] != 0:
                flat.add((r, int(e.er_cols[s, k]), float(e.er_vals[s, k])))
    grouped = set()
    p_, ep, we = g["er_p_vals"].shape
    for p in range(p_):
        for s in range(ep):
            for k in range(we):
                val = g["er_p_vals"][p, s, k]
                if val != 0:
                    grouped.add((p * v + int(g["er_p_rows"][p, s]),
                                 int(g["er_p_cols"][p, s, k]), float(val)))
    assert flat == grouped


def test_bucketed_device_is_jittable_pytree(rng):
    """EHYBBucketsDevice round-trips through tree flatten/unflatten and its
    jitted apply neither re-uploads nor retraces across calls."""
    import jax

    from repro.core import (EHYBBucketsDevice, build_buckets,
                            ehyb_buckets_spmv)

    m = unstructured(512, 10)
    e = build_ehyb(m)
    dev = EHYBBucketsDevice.from_buckets(build_buckets(e))
    leaves, treedef = jax.tree_util.tree_flatten(dev)
    dev2 = jax.tree_util.tree_unflatten(treedef, leaves)
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    y1 = np.asarray(ehyb_buckets_spmv(dev, x))
    y2 = np.asarray(ehyb_buckets_spmv(dev2, x))
    np.testing.assert_array_equal(y1, y2)
    y_ref = m.spmv(np.asarray(x, np.float64))
    assert np.abs(y1 - y_ref).max() / max(np.abs(y_ref).max(), 1) < 1e-4


def test_sparse_linear_space_threading(rng):
    """SparseLinear's permuted-space call chain == the original-space call."""
    from repro.core.sparse_linear import SparseLinear

    w = rng.standard_normal((96, 128))
    lin = SparseLinear.from_dense(w, density=0.2, format="ehyb")
    assert lin.supports_permuted
    x = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
    y1 = np.asarray(lin(x))
    y2 = np.asarray(lin.from_permuted(lin(lin.to_permuted(x),
                                          space="permuted")))
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)


def test_dist_spmv_permuted_space(rng):
    """The distributed path's permuted-space function matches the
    single-device permuted apply (degenerate 1-device mesh)."""
    from repro.compat import make_mesh
    from repro.core.dist_spmv import build_dist_spmv

    m = poisson3d(8)
    op = build_spmv(m, format="ehyb")
    mesh = make_mesh((1,), ("data",))
    dist_p = build_dist_spmv(op, mesh, "data", space="permuted")
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    x_new = op.to_permuted(x)
    np.testing.assert_allclose(np.asarray(dist_p(x_new)),
                               np.asarray(op.matvec_permuted(x_new)),
                               rtol=1e-5, atol=1e-5)


def test_permuted_precond_keyed_by_partitioning(rng):
    """Operators over the same matrix with different partitionings (hence
    different perms/n_pad) must each get their own permuted preconditioner
    (regression: a (matrix, kind)-only cache key shared one diagonal)."""
    from repro.core import cg, precond_for

    m = unstructured(200, 8)
    e1 = build_ehyb(m, n_parts=4, vec_size=56)
    op1 = build_spmv(m, format="ehyb", shared={"ehyb": e1})
    op2 = build_spmv(m, format="ehyb")         # default partitioning
    assert op1.n_pad != op2.n_pad or not np.array_equal(
        np.asarray(op1.obj.perm), np.asarray(op2.obj.perm))
    b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    for op in (op1, op2):
        pre = precond_for(m, "jacobi", op, space="permuted")
        r = cg(op.matvec_permuted, op.to_permuted(b), pre, tol=1e-5,
               max_iters=1000)
        x = np.asarray(op.from_permuted(r.x), np.float64)
        rel = np.linalg.norm(m.spmv(x) - np.asarray(b)) / \
            np.linalg.norm(np.asarray(b))
        assert rel < 1e-3


def test_solver_context_reduces_modeled_bytes():
    """Acceptance: solver-context EHYB traffic == spmv-context minus the
    2·n_pad·val_bytes perm round trip, for every EHYB-family format."""
    m = poisson3d(8)
    e = build_ehyb(m)
    shared = {"ehyb": e}
    for fmt in EHYB_FAMILY:
        one = at.estimate_bytes(m, fmt, 4, dict(shared), context="spmv")
        it = at.estimate_bytes(m, fmt, 4, dict(shared), context="solver")
        assert one - it == 2 * e.n_pad * 4, fmt
