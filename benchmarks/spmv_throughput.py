"""Paper Fig. 2/3 (fp32) and Fig. 4/5 (fp64): SpMV throughput per matrix per
format.

The paper reports GFLOP/s on a V100; this container is CPU-only, so the
*relative* ordering across formats (same XLA backend, same matrix) is the
reproducible quantity — plus the modeled TPU bytes (benchmarks/bytes_model.py)
which is hardware-independent.  GFLOP/s = 2·nnz / t.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import build_formats, emit, get_matrix, time_fn


def run(dtype_name: str = "f32", suite=None):
    from repro.core import SUITE

    dtype = jnp.float32 if dtype_name == "f32" else jnp.float64
    rows = {}
    for name in (suite or SUITE):
        m = get_matrix(name)
        x = jnp.asarray(np.random.default_rng(0).standard_normal(m.n),
                        dtype=dtype)
        y_ref = m.spmv(np.asarray(x, dtype=np.float64))
        scale = np.abs(y_ref).max() + 1e-30
        rows[name] = {}
        for fmt, (obj, fn) in build_formats(name, dtype).items():
            t = time_fn(fn, obj, x)
            y = np.asarray(fn(obj, x), dtype=np.float64)
            err = np.abs(y - y_ref).max() / scale
            gflops = 2.0 * m.nnz / t / 1e9
            rows[name][fmt] = (t, gflops, err)
            emit(f"spmv_{dtype_name}/{name}/{fmt}", t * 1e6,
                 f"gflops={gflops:.3f};relerr={err:.1e};nnz={m.nnz}")
    return rows


def main():
    rows32 = run("f32")
    with jax.enable_x64():
        rows64 = run("f64")
    return {"f32": rows32, "f64": rows64}


if __name__ == "__main__":
    main()
