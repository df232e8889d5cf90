"""Batched SpMM path (ISSUE 6): conformance of the multi-rhs apply vs the
dense oracle for every registered format × k × dtype, the SpMM megakernels,
batched VJP grad checks, a zero-recompile probe for the k-batched apply, and
the cost model's k axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.autotune import available_formats, estimate_bytes
from repro.core import build_ehyb, poisson3d, powerlaw
from repro.core.matrices import SparseCSR


def _mat(kind: str) -> SparseCSR:
    return poisson3d(6) if kind == "stencil" else powerlaw(192, 6)


# ---------------------------------------------------------------------------
# conformance: every registered format × k × dtype vs the dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["stencil", "powerlaw"])
@pytest.mark.parametrize("k", [1, 4, 32])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-5),
                                       (jnp.bfloat16, 5e-2)])
def test_spmm_conformance_all_formats(kind, k, dtype, tol, rng):
    m = _mat(kind)
    X = rng.standard_normal((m.n, k))
    ref = m.to_dense() @ X                       # float64 oracle
    scale = np.abs(ref).max() + 1e-30
    Xd = jnp.asarray(X, dtype)
    for fmt in available_formats():
        p = api.plan(m, execution=api.ExecutionConfig(format=fmt,
                                                      dtype=dtype, k=k))
        op = p.bind(m)
        Y = np.asarray(op @ Xd, np.float64)
        assert Y.shape == (m.n, k)
        err = np.abs(Y - ref).max() / scale
        assert err < tol, (fmt, kind, k, err)


@pytest.mark.parametrize("rhs_chunk", [16, 2])
def test_spmm_megakernel_matches_oracle(rhs_chunk, rng):
    """The Pallas SpMM apply (packed kernel over all rhs + XLA ER), at a k
    that exercises the rhs-chunk remainder when the chunk is 2."""
    from repro.core import EHYBPackedDevice, pack_staircase
    from repro.kernels import ehyb_spmv_packed_pallas

    m = powerlaw(192, 6)
    dev = EHYBPackedDevice.from_packed(
        pack_staircase(build_ehyb(m)), kparams=(("rhs_chunk", rhs_chunk),))
    X = jnp.asarray(rng.standard_normal((m.n, 5)), jnp.float32)
    Y = np.asarray(ehyb_spmv_packed_pallas(dev, X, interpret=True),
                   np.float64)
    ref = m.to_dense() @ np.asarray(X, np.float64)
    scale = np.abs(ref).max() + 1e-30
    assert np.abs(Y - ref).max() / scale < 5e-5


def test_spmm_matches_column_by_column_spmv(rng):
    """The batched apply is numerically the same computation as k single
    applies — the megakernel only amortizes the A-stream."""
    m = poisson3d(6)
    op = api.plan(m).bind(m)
    X = jnp.asarray(rng.standard_normal((m.n, 8)), jnp.float32)
    Y = np.asarray(op @ X)
    cols = np.stack([np.asarray(op @ X[:, j]) for j in range(8)], axis=1)
    np.testing.assert_allclose(Y, cols, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# batched custom-VJP
# ---------------------------------------------------------------------------

def test_batched_vjp_wrt_x_matches_dense(rng):
    m = poisson3d(6)
    d = m.to_dense()
    X = jnp.asarray(rng.standard_normal((m.n, 4)), jnp.float32)
    V = jnp.asarray(rng.standard_normal((m.n, 4)), jnp.float32)
    op = api.plan(m).bind(m)
    gX = jax.grad(lambda xx: jnp.vdot(op @ xx, V))(X)
    gX_ref = d.T @ np.asarray(V, np.float64)
    scale = max(np.abs(gX_ref).max(), 1e-12)
    np.testing.assert_allclose(np.asarray(gX, np.float64) / scale,
                               gX_ref / scale, rtol=1e-5, atol=1e-5)


def test_batched_vjp_wrt_values_matches_dense(rng):
    m = powerlaw(192, 6)
    X = jnp.asarray(rng.standard_normal((m.n, 4)), jnp.float32)
    V = jnp.asarray(rng.standard_normal((m.n, 4)), jnp.float32)
    vals = jnp.asarray(m.data, jnp.float32)
    p = api.plan(m)
    gv = jax.grad(lambda vv: jnp.vdot(p.bind(vv) @ X, V))(vals)
    rows = np.repeat(np.arange(m.n), m.row_lengths())
    gv_ref = np.einsum("kr,kr->k", np.asarray(V, np.float64)[rows],
                       np.asarray(X, np.float64)[m.indices])
    scale = max(np.abs(gv_ref).max(), 1e-12)
    np.testing.assert_allclose(np.asarray(gv, np.float64) / scale,
                               gv_ref / scale, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# zero-recompile: rebinding values must not re-trace the k-batched apply
# ---------------------------------------------------------------------------

def test_rebinding_values_does_not_retrace_batched_apply(rng):
    m1 = poisson3d(6)
    m2 = SparseCSR(m1.n, m1.indptr, m1.indices, m1.data * 1.7)
    p = api.plan(m1, execution=api.ExecutionConfig(format="ehyb", k=8))
    op1 = p.bind(m1)
    X = jnp.asarray(rng.standard_normal((m1.n, 8)), jnp.float32)
    jax.block_until_ready(op1 @ X)
    jax.block_until_ready(op1._diff_apply()(op1.obj, X))
    probes = [getattr(p._raw_apply(), "_cache_size", None),
              getattr(op1._diff_apply(), "_cache_size", None)]
    if any(pr is None for pr in probes):
        pytest.skip("jit cache-size probe unavailable on this jax")
    n0 = [pr() for pr in probes]
    op2 = p.bind(m2)
    jax.block_until_ready(op2 @ X)
    jax.block_until_ready(op2._diff_apply()(op2.obj, X))
    assert [pr() for pr in probes] == n0, \
        "rebinding values must hit the existing jit caches at k=8"


# ---------------------------------------------------------------------------
# cost model: the k axis
# ---------------------------------------------------------------------------

def test_bytes_moved_k_axis_amortizes_the_A_stream():
    m = powerlaw(192, 6)
    e = build_ehyb(m)
    b1 = e.bytes_moved(4, k=1)
    b8 = e.bytes_moved(4, k=8)
    assert b8["ell"] == b1["ell"], "A-stream bytes must not scale with k"
    assert b8["x_cache"] == 8 * b1["x_cache"]
    assert b8["y"] == 8 * b1["y"]
    assert b8["total"] < 8 * b1["total"], \
        "one k=8 SpMM must move fewer modeled bytes than 8 SpMVs"
    for fmt in available_formats():
        assert estimate_bytes(m, fmt, 4, k=8) > estimate_bytes(m, fmt, 4,
                                                               k=1), fmt


def test_k_moves_the_format_crossover():
    """x/y-light formats amortize better: dense's modeled bytes grow slower
    in k than the gather-heavy CSR stream's, so relative standings shift
    with batch width (the SpMM crossover plan() ranks at)."""
    m = powerlaw(192, 6)

    def ratio(fmt):
        return estimate_bytes(m, fmt, 4, k=64) / estimate_bytes(m, fmt, 4,
                                                                k=1)

    assert ratio("dense") < ratio("csr")
    assert ratio("ehyb") < ratio("csr")


def test_execution_config_k_is_part_of_plan_identity():
    m = poisson3d(6)
    p1 = api.plan(m, execution=api.ExecutionConfig())
    p8 = api.plan(m, execution=api.ExecutionConfig(k=8))
    assert p1 is not p8
    assert api.plan(m, execution=api.ExecutionConfig(k=8)) is p8
    with pytest.raises(ValueError):
        api.ExecutionConfig(k=0)
