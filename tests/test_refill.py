"""Value-refresh fast path: same pattern + new values must refill — not
rebuild, not recompile — and match a from-scratch build bit-for-bit.

Also the operator-reuse bugfix regressions that ride along:
  * measured autotuning with ``context="solver"`` times the permuted-space
    apply (not the original-space one whose per-call perm round trip
    pollutes solver-ranked timings);
  * the diagonal-preconditioner closure carries fp64 solves at fp64;
  * ``matrix_key`` distinguishes value buffers with identical bytes but
    different dtypes;
  * an integer rhs never builds integer value tables.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import autotune as at
from repro.core import build_ehyb, build_spmv, poisson3d, powerlaw, solve, spmv
from repro.core import counters
from repro.core.matrices import SparseCSR
from repro.core.solver import _diag_closure


def _with_new_values(m: SparseCSR, seed: int = 7) -> SparseCSR:
    data = np.random.default_rng(seed).standard_normal(m.nnz)
    return SparseCSR(m.n, m.indptr, m.indices, data)


STRUCTURE_COUNTERS = ("partition", "build_ehyb", "pack_staircase",
                      "build_buckets")


def _structure_work(before: dict, after: dict) -> dict:
    return {c: after.get(c, 0) - before.get(c, 0) for c in STRUCTURE_COUNTERS
            if after.get(c, 0) != before.get(c, 0)}


# ---------------------------------------------------------------------------
# refill equivalence: every format × fp32/fp64, bit-identical device tables
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", sorted(at.available_formats()))
@pytest.mark.parametrize("dtype_name", ["float32", "float64"])
@pytest.mark.parametrize("gen", ["stencil", "powerlaw"])
def test_refill_matches_fresh_build_bit_identical(fmt, dtype_name, gen):
    m1 = poisson3d(6) if gen == "stencil" else powerlaw(256, 4)
    m2 = _with_new_values(m1)
    with jax.enable_x64(dtype_name == "float64"):
        dtype = jnp.dtype(dtype_name)
        op1 = build_spmv(m1, fmt, dtype)
        op2 = op1.update_values(m2)
        # fresh from-scratch build (shared dict pins a scratch host EHYB so
        # the global pattern cache cannot itself serve a refill here)
        fresh = build_spmv(m2, fmt, dtype, shared={"ehyb": build_ehyb(m2)})
        l_refill = jax.tree_util.tree_leaves(op2.obj)
        l_fresh = jax.tree_util.tree_leaves(fresh.obj)
        assert len(l_refill) == len(l_fresh)
        for a, b in zip(l_refill, l_fresh):
            assert a.dtype == b.dtype
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # refilled operator computes the new matrix
        if at.get_format(fmt).kernel == "xla":
            x = jnp.asarray(np.random.default_rng(0).standard_normal(m1.n),
                            dtype)
            y = np.asarray(op2(x), np.float64)
            y_ref = m2.spmv(np.asarray(x, np.float64))
            np.testing.assert_allclose(y, y_ref, rtol=5e-5, atol=5e-5)


def test_update_values_rejects_pattern_change():
    op = build_spmv(poisson3d(6), "csr")
    other = poisson3d(8)
    with pytest.raises(ValueError):
        op.update_values(other)


# ---------------------------------------------------------------------------
# amortization guarantees: zero structure passes, zero recompilation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["ehyb", "ehyb_bucketed", "ehyb_packed"])
def test_refill_triggers_zero_partitioning_or_packing(fmt):
    m1 = powerlaw(256, 4)
    m2 = _with_new_values(m1)
    op1 = build_spmv(m1, fmt)
    before = counters.snapshot()
    op2 = op1.update_values(m2)
    after = counters.snapshot()
    assert _structure_work(before, after) == {}
    assert after.get("ehyb_refill", 0) == before.get("ehyb_refill", 0) + 1
    # structural device arrays are shared by reference, not re-uploaded
    if fmt == "ehyb":
        assert op2.obj.ell_cols is op1.obj.ell_cols
        assert op2.obj.perm is op1.obj.perm
    elif fmt == "ehyb_packed":
        assert op2.obj.packed_cols is op1.obj.packed_cols
        assert op2.obj.col_starts is op1.obj.col_starts
    else:
        assert all(c2 is c1 for c1, c2 in zip(op1.obj.cols, op2.obj.cols))


def test_refill_never_calls_build_ehyb(monkeypatch):
    """Monkeypatch proof: the whole update path works with build_ehyb gone."""
    import repro.autotune.registry as registry
    import repro.core.ehyb as ehyb_mod

    m1 = poisson3d(6)
    m2 = _with_new_values(m1)
    op1 = build_spmv(m1, "ehyb")

    def boom(*a, **k):
        raise AssertionError("build_ehyb must not run on a value-only update")

    monkeypatch.setattr(registry, "build_ehyb", boom)
    monkeypatch.setattr(ehyb_mod, "build_ehyb", boom)
    op2 = op1.update_values(m2)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(m1.n),
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(op2(x), np.float64),
                               m2.spmv(np.asarray(x, np.float64)),
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("fmt", ["csr", "ehyb", "ehyb_bucketed"])
def test_refill_triggers_zero_recompilation(fmt):
    m1 = poisson3d(6)
    m2 = _with_new_values(m1)
    op1 = build_spmv(m1, fmt)
    jax.block_until_ready(op1(jnp.ones(m1.n, jnp.float32)))
    probe = getattr(op1.apply, "_cache_size", None)
    if probe is None:
        pytest.skip("jit cache-size probe unavailable on this jax")
    n0 = probe()
    op2 = op1.update_values(m2)
    jax.block_until_ready(op2(jnp.ones(m1.n, jnp.float32)))
    assert probe() == n0


def test_cached_spmv_operator_refills_on_value_only_change():
    m1 = poisson3d(6)
    m2 = _with_new_values(m1)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(m1.n),
                    jnp.float32)
    y1 = spmv(m1, x, format="ehyb")
    before = counters.snapshot()
    y2 = spmv(m2, x, format="ehyb")
    after = counters.snapshot()
    assert _structure_work(before, after) == {}
    assert after.get("ehyb_refill", 0) > before.get("ehyb_refill", 0)
    np.testing.assert_allclose(np.asarray(y2, np.float64),
                               m2.spmv(np.asarray(x, np.float64)),
                               rtol=5e-5, atol=5e-5)
    # and an exact repeat stays a pure cache hit (same operator object)
    from repro.core.spmv import cached_spmv_operator

    assert cached_spmv_operator(m2, "ehyb", jnp.float32) is \
        cached_spmv_operator(m2, "ehyb", jnp.float32)


def test_solve_reuses_structure_across_value_updates():
    """Transient-FEM shape: re-solve with updated values on a fixed pattern
    must not re-run the partition/reorder pipeline, and must see the new
    matrix (scaled A ⇒ scaled-down x)."""
    m1 = poisson3d(6)
    m2 = SparseCSR(m1.n, m1.indptr, m1.indices, m1.data * 2.0)
    b = jnp.asarray(np.random.default_rng(2).standard_normal(m1.n),
                    jnp.float32)
    r1 = solve(m1, b, tol=1e-8)
    before = counters.snapshot()
    r2 = solve(m2, b, tol=1e-8)
    after = counters.snapshot()
    assert _structure_work(before, after) == {}
    assert bool(r1.converged) and bool(r2.converged)
    np.testing.assert_allclose(np.asarray(r2.x), np.asarray(r1.x) / 2.0,
                               rtol=1e-4, atol=1e-5)


def test_sparse_linear_update_values_refills():
    from repro.core.sparse_linear import SparseLinear

    rng = np.random.default_rng(0)
    w1 = rng.standard_normal((24, 48))
    lin = SparseLinear.from_dense(w1, density=0.25, format="ehyb")
    before = counters.snapshot()
    lin2 = lin.update_values(w1 * 3.0)
    after = counters.snapshot()
    assert _structure_work(before, after) == {}
    x = jnp.asarray(rng.standard_normal((2, 48)), jnp.float32)
    np.testing.assert_allclose(np.asarray(lin2(x)), 3.0 * np.asarray(lin(x)),
                               rtol=1e-4, atol=1e-4)
    assert lin2.ehyb is not None and lin2.op.obj.perm is lin.op.obj.perm


# ---------------------------------------------------------------------------
# bugfix regressions
# ---------------------------------------------------------------------------

def test_measured_solver_context_times_permuted_apply(monkeypatch):
    """autotune(mode="measure", context="solver") must time the operation
    the solver loop runs — the permuted-space apply on an (n_pad,) vector —
    not the original-space apply with its per-call perm round trip."""
    import repro.autotune.tuner as tuner

    calls = []

    def spy(apply, obj, x, **kw):
        calls.append((apply, obj, x))
        return 1.0

    monkeypatch.setattr(tuner, "_time_spmv", spy)
    m = poisson3d(8)
    at.autotune(m, mode="measure", context="solver",
                candidates=["ehyb", "csr"], top_k=2, use_cache=False)
    spec = at.get_format("ehyb")
    (apply_ehyb, obj_ehyb, x_ehyb), = [
        c for c in calls if hasattr(c[1], "n_pad")]
    assert apply_ehyb is spec.permuted    # not the original-space ehyb_spmv
    assert x_ehyb.shape[0] == obj_ehyb.n_pad   # permuted padded input
    # non-permuted formats still time the original-space apply on (n,)
    (apply_csr, _, x_csr), = [c for c in calls if not hasattr(c[1], "n_pad")]
    assert x_csr.shape[0] == m.n


def test_diag_precond_closure_preserves_fp64():
    inv = np.full(16, 0.5)
    with jax.enable_x64():
        r64 = jnp.ones(16, jnp.float64)
        assert _diag_closure(inv)(r64).dtype == jnp.float64
    r32 = jnp.ones(16, jnp.float32)
    assert _diag_closure(inv)(r32).dtype == jnp.float32


def test_fp64_solve_stays_fp64_end_to_end():
    m = poisson3d(6)
    with jax.enable_x64():
        b = jnp.asarray(np.random.default_rng(3).standard_normal(m.n),
                        jnp.float64)
        r = solve(m, b, precond="jacobi", format="csr", tol=1e-12,
                  max_iters=800)
        assert r.x.dtype == jnp.float64
        assert bool(r.converged)
        x_ref = np.linalg.solve(m.to_dense(), np.asarray(b))
        np.testing.assert_allclose(np.asarray(r.x), x_ref, rtol=1e-8,
                                   atol=1e-8)


def test_matrix_key_distinguishes_dtypes_with_identical_bytes():
    m = poisson3d(4)
    m_f32 = SparseCSR(m.n, m.indptr, m.indices, np.zeros(m.nnz, np.float32))
    m_i32 = SparseCSR(m.n, m.indptr, m.indices, np.zeros(m.nnz, np.int32))
    assert m_f32.data.tobytes() == m_i32.data.tobytes()
    assert at.matrix_key(m_f32) != at.matrix_key(m_i32)


def test_integer_rhs_promotes_to_float_operator():
    m = poisson3d(6)
    x_int = jnp.ones(m.n, jnp.int32)
    y = spmv(m, x_int, format="csr")
    assert jnp.issubdtype(y.dtype, jnp.floating)
    np.testing.assert_allclose(np.asarray(y, np.float64),
                               m.spmv(np.ones(m.n)), rtol=1e-5, atol=1e-5)
