"""Every SpMV format path vs the numpy oracle, fp32 + fp64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (COODevice, EHYBDevice, ELLDevice, HYBDevice,
                        build_buckets, build_ehyb, coo_spmv, ehyb_spmv,
                        ehyb_spmv_buckets, ell_spmv, hyb_spmv, poisson3d,
                        powerlaw, unstructured)

MATS = {
    "poisson": lambda: poisson3d(8),
    "unstruct": lambda: unstructured(1024, 10),
    "powerlaw": lambda: powerlaw(1024, 6),
}


@pytest.mark.parametrize("mat", list(MATS))
def test_all_formats_fp32(mat, rng):
    m = MATS[mat]()
    x = jnp.asarray(rng.standard_normal(m.n), dtype=jnp.float32)
    y_ref = m.spmv(np.asarray(x, dtype=np.float64))
    tol = 1e-4 * max(np.abs(y_ref).max(), 1.0)
    e = build_ehyb(m)
    paths = {
        "coo": (COODevice.from_csr(m), coo_spmv),
        "ell": (ELLDevice.from_csr(m), ell_spmv),
        "hyb": (HYBDevice.from_csr(m), hyb_spmv),
        "ehyb": (EHYBDevice.from_ehyb(e), ehyb_spmv),
    }
    for name, (dev, fn) in paths.items():
        y = np.asarray(fn(dev, x), dtype=np.float64)
        np.testing.assert_allclose(y, y_ref, atol=tol, err_msg=name)
    y = np.asarray(ehyb_spmv_buckets(build_buckets(e), x))
    np.testing.assert_allclose(y, y_ref, atol=tol, err_msg="buckets")


def test_ehyb_fp64(rng):
    with jax.enable_x64():
        m = poisson3d(6)
        e = build_ehyb(m)
        dev = EHYBDevice.from_ehyb(e, dtype=jnp.float64)
        x = jnp.asarray(rng.standard_normal(m.n), dtype=jnp.float64)
        y = np.asarray(ehyb_spmv(dev, x))
        np.testing.assert_allclose(y, m.spmv(np.asarray(x)), rtol=1e-12)


def test_ehyb_spmm_matches_column_spmv(rng):
    m = unstructured(512, 8)
    dev = EHYBDevice.from_ehyb(build_ehyb(m))
    xs = jnp.asarray(rng.standard_normal((m.n, 5)), dtype=jnp.float32)
    ys = np.asarray(ehyb_spmv(dev, xs))
    for j in range(5):
        yj = np.asarray(ehyb_spmv(dev, xs[:, j]))
        np.testing.assert_allclose(ys[:, j], yj, rtol=2e-5, atol=1e-5)


def test_max_width_cap_preserves_product(rng):
    m = powerlaw(512, 8)
    x = jnp.asarray(rng.standard_normal(m.n), dtype=jnp.float32)
    y_ref = m.spmv(np.asarray(x, dtype=np.float64))
    e = build_ehyb(m, n_parts=4, vec_size=128, max_width=8)
    y = np.asarray(ehyb_spmv(EHYBDevice.from_ehyb(e), x), dtype=np.float64)
    np.testing.assert_allclose(y, y_ref, atol=1e-3 * np.abs(y_ref).max())
