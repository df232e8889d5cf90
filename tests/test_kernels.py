"""Pallas kernel sweeps: shapes × dtypes, allclose vs the ref.py jnp oracle
(interpret mode executes the kernel body on CPU)."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EHYBDevice, EHYBPackedDevice, build_ehyb, ehyb_spmv,
                        pack_staircase, poisson3d, unstructured)
from repro.core.spmv import _fused_er_parts
from repro.kernels import ehyb_packed_pallas, ehyb_spmv_packed_pallas, ref


def _rand_staircase(rng, p, v, w, r, dtype):
    """Random partitions in EHYB's sliced-ELL shape: row widths sorted
    descending inside each partition, entries in each row's first columns,
    local columns anywhere in [0, V)."""
    x_parts = rng.standard_normal((p, v, r)).astype(dtype)
    widths = -np.sort(-rng.integers(0, w + 1, size=(p, v)), axis=1)
    vals = (rng.standard_normal((p, v, w))
            * (np.arange(w)[None, None, :] < widths[:, :, None]))
    cols = rng.integers(0, v, size=(p, v, w)).astype(np.uint16)
    e = types.SimpleNamespace(n_parts=p, vec_size=v, ell_width=w,
                              fill_plan=None, ell_vals=vals, ell_cols=cols,
                              preprocess_seconds={})
    return jnp.asarray(x_parts), pack_staircase(e), vals, cols


@pytest.mark.parametrize("v,w,r", [(8, 1, 1), (64, 3, 1), (64, 17, 4),
                                   (512, 7, 1), (128, 33, 2), (2048, 5, 1)])
@pytest.mark.parametrize("dtype,tol", [(np.float32, 2e-5), ("bfloat16", 3e-2)])
def test_ell_kernel_sweep(v, w, r, dtype, tol, rng):
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    x, pk, vals, cols = _rand_staircase(rng, 4, v, w, r, np.float32)
    x = x.astype(dt)
    out = ehyb_packed_pallas(x, jnp.asarray(pk.packed_vals, dt),
                             jnp.asarray(pk.packed_cols),
                             jnp.asarray(pk.col_starts),
                             jnp.asarray(pk.col_rows), interpret=True)
    expect = ref.ehyb_ell_ref(x.astype(jnp.float32),
                              jnp.asarray(vals, dt).astype(jnp.float32),
                              jnp.asarray(cols))
    scale = float(jnp.max(jnp.abs(expect))) + 1e-30
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - expect))) / scale
    assert err < tol, (v, w, r, dtype, err)


@pytest.mark.parametrize("rows,w,r", [(8, 1, 1), (64, 9, 1), (256, 5, 4)])
def test_er_kernel_sweep(rows, w, r, rng):
    """The ER stage (XLA, per owning partition) == gather + row dot +
    scatter into the partition's block."""
    p, v = 4, 512
    x = jnp.asarray(rng.standard_normal((p * v, r)), dtype=jnp.float32)
    vals = jnp.asarray(rng.standard_normal((p, rows, w)), dtype=jnp.float32)
    cols = jnp.asarray(rng.integers(0, p * v, (p, rows, w)), dtype=jnp.int32)
    local = np.stack([rng.permutation(v)[:rows] for _ in range(p)])
    out = _fused_er_parts(x, vals, cols, jnp.asarray(local, jnp.int32), v)
    expect = np.zeros((p, v, r), np.float32)
    for q in range(p):
        expect[q, local[q]] = np.asarray(ref.er_ref(x, vals[q], cols[q]))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("gen", [lambda: poisson3d(8),
                                 lambda: unstructured(1024, 12)])
@pytest.mark.parametrize("k", [1, 3])
def test_full_kernel_vs_jnp_path(gen, k, rng):
    m = gen()
    e = build_ehyb(m)
    dev = EHYBPackedDevice.from_packed(pack_staircase(e))
    shape = (m.n,) if k == 1 else (m.n, k)
    x = jnp.asarray(rng.standard_normal(shape), dtype=jnp.float32)
    y_k = np.asarray(ehyb_spmv_packed_pallas(dev, x, interpret=True))
    y_j = np.asarray(ehyb_spmv(EHYBDevice.from_ehyb(e), x))
    np.testing.assert_allclose(y_k, y_j, rtol=1e-4, atol=1e-4)
    y_ref = m.to_dense() @ np.asarray(x, dtype=np.float64)
    np.testing.assert_allclose(y_k, y_ref, atol=1e-4 * np.abs(y_ref).max())


def test_kernel_spmm(rng):
    m = poisson3d(8)
    e = build_ehyb(m)
    dev = EHYBPackedDevice.from_packed(pack_staircase(e))
    xs = jnp.asarray(rng.standard_normal((m.n, 4)), dtype=jnp.float32)
    y = np.asarray(ehyb_spmv_packed_pallas(dev, xs, interpret=True))
    np.testing.assert_allclose(y, np.asarray(ehyb_spmv(
        EHYBDevice.from_ehyb(e), xs)), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("gen", [lambda: poisson3d(8),
                                 lambda: unstructured(1024, 12)])
def test_packed_kernel_v2(gen, rng):
    """Packed staircase == oracle, and it stores no more than the uniform
    tile layout once a partition spans whole tiles (here V = 256 = one
    (2, 128) tile)."""
    m = gen()
    e = build_ehyb(m, n_parts=m.n // 256, vec_size=256)
    pk = pack_staircase(e)
    dev2 = EHYBPackedDevice.from_packed(pk)
    x = jnp.asarray(rng.standard_normal(m.n), dtype=jnp.float32)
    y2 = np.asarray(ehyb_spmv_packed_pallas(dev2, x, interpret=True))
    y_ref = m.spmv(np.asarray(x, dtype=np.float64))
    np.testing.assert_allclose(y2, y_ref, atol=1e-4 * np.abs(y_ref).max())
    assert (pk.bytes_moved(4)["total"]
            <= e.bytes_moved(4, layout="tile")["total"] + 8 * m.n)
