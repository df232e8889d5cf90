"""ER window against the XLA ER path on patterns the benchmark cells do not
run: time one ``ehyb_packed`` SpMV per pattern and partition strategy.

The patterns are a random geometric graph (``unstructured``, rows in random
order) under ``natural`` partitions, whose ER reads all of x, and under
``bfs`` partitions, whose ER is local but wider than a window holds; and a
slab of HPCG's 104 × 104 planes, the cells' own case.  Each line gives
the median and 95th-percentile milliseconds of an apply (each call ended
by ``block_until_ready``), its error against the float64 ``scipy.sparse``
product, and how the ER split between window and leftover (None on a
program without ER windows, so the same script times a parent checkout).

Usage (on a TPU; on the CPU the kernel runs interpreted, so keep n small):
  PYTHONPATH=src:. python -m benchmarks.er_window [--n 200000] [--applies 50]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from repro.core import EHYBPackedDevice, build_ehyb, pack_staircase
from repro.core.matrices import _stencil_matrix, unstructured
from repro.kernels import ehyb_spmv_packed_pallas


def _hpcg_slab(planes: int):
    offsets = [(a, b, c) for a in (-1, 0, 1) for b in (-1, 0, 1)
               for c in (-1, 0, 1)]
    return _stencil_matrix(planes, 104, 104, offsets, 1)


def _window(e) -> dict | None:
    w = getattr(e, "_er_window", None)
    if w is None:
        return None
    return {"lane_rows": w.lane_rows, "entries": w.entries,
            "leftover": w.leftover}


def run_one(name: str, m, method: str, applies: int, seed: int) -> dict:
    t0 = time.perf_counter()
    e = build_ehyb(m, method=method)
    dev = EHYBPackedDevice.from_packed(pack_staircase(e))
    setup_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(m.n)
    xj = jnp.asarray(x, jnp.float32)
    jax.block_until_ready(ehyb_spmv_packed_pallas(dev, xj))     # compile
    ms = []
    for _ in range(applies):
        t = time.perf_counter()
        y = ehyb_spmv_packed_pallas(dev, xj)
        jax.block_until_ready(y)
        ms.append((time.perf_counter() - t) * 1e3)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=(m.n, m.n))
    y_ref = a @ x
    err = float(np.abs(np.asarray(y, np.float64) - y_ref).max()
                / np.abs(y_ref).max())
    return {"pattern": name, "partition": method, "n": m.n, "nnz": m.nnz,
            "parts": e.n_parts, "er_entries": int(np.count_nonzero(e.er_vals)),
            "apply_ms_median": float(np.median(ms)),
            "apply_ms_p95": float(np.percentile(ms, 95)),
            "err": err, "setup_s": setup_s, "window": _window(e),
            "device": jax.devices()[0].device_kind}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=200_000,
                    help="rows of the random geometric graph")
    ap.add_argument("--planes", type=int, default=12,
                    help="planes of the 104 x 104 HPCG slab")
    ap.add_argument("--applies", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    graph = unstructured(args.n)
    for name, m, method in (("unstructured", graph, "natural"),
                            ("unstructured", graph, "bfs"),
                            ("hpcg_slab", _hpcg_slab(args.planes),
                             "natural")):
        print(json.dumps(run_one(name, m, method, args.applies, args.seed)),
              flush=True)


if __name__ == "__main__":
    main()
