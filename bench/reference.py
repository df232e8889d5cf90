"""The plain reference: a float64 ``scipy.sparse`` CSR built from the
generator's arrays, and the numbers the checks compare.  Imports nothing of
``repro`` and takes nothing the program made."""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def csr64(g: dict) -> sp.csr_matrix:
    """Its own float64 copy of the generated matrix."""
    n = int(g["n"])
    return sp.csr_matrix((np.array(g["data"], np.float64),
                          np.array(g["indices"]), np.array(g["indptr"])),
                         shape=(n, n))


def apply_error(a64: sp.csr_matrix, x, y) -> float:
    """Max-norm error of ``y`` against ``A @ x`` in float64, relative to
    the largest entry of ``A @ x``."""
    ref = a64 @ np.asarray(x, np.float64)
    err = np.abs(np.asarray(y, np.float64) - ref).max()
    return float(err / max(np.abs(ref).max(), 1e-300))


def rounder(dtype=None):
    """A function that rounds a float64 array to ``dtype`` (a numpy or
    ``ml_dtypes`` name) and back; the identity for ``None``."""
    if dtype is None:
        return lambda v: v
    import ml_dtypes

    dt = np.dtype(getattr(ml_dtypes, dtype, dtype))
    return lambda v: np.asarray(v).astype(dt).astype(np.float64)


def cg(a64: sp.csr_matrix, b, iterations: int, rnd=rounder()) -> np.ndarray:
    """``x`` after ``iterations`` steps of CG with the Jacobi preconditioner
    from ``x0 = 0``, in float64; ``rnd`` rounds every vector the loop
    stores (the lower-precision control), the dots stay float64."""
    b = rnd(np.asarray(b, np.float64))
    inv = rnd(1.0 / a64.diagonal())
    x = np.zeros_like(b)
    r = b.copy()
    p = rnd(inv * r)
    rz = r @ p
    for _ in range(iterations):
        ap = rnd(a64 @ p)
        alpha = rz / (p @ ap)
        x = rnd(x + alpha * p)
        r = rnd(r - alpha * ap)
        z = rnd(inv * r)
        rz, rz_old = r @ z, rz
        p = rnd(z + (rz / rz_old) * p)
    return x


def relative_error(x, ref) -> float:
    """``‖x − ref‖₂ / ‖ref‖₂`` in float64."""
    ref = np.asarray(ref, np.float64)
    err = np.linalg.norm(np.asarray(x, np.float64) - ref)
    return float(err / max(np.linalg.norm(ref), 1e-300))
