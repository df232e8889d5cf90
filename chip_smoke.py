#!/usr/bin/env python3
"""Chip smoke test: the EHYB main path on a TPU at HPCG's deployment size.

    python3 chip_smoke.py             # one chip: plan -> bind -> @ / solve
    python3 chip_smoke.py --chips 4   # only the sharded path on 4 chips,
                                      # against the one-device solve

The matrix is ``symmetrize(poisson3d27(104))``: HPCG's reference local grid
of 104³ (1,124,864 rows, 29,791,000 nonzeros), built from its seed.  The
one-chip run plans it for the Pallas EHYB format (``ehyb_packed``) in the
solver workload, binds f32 values and checks, against float64 products on
the host:

  * ``op @ x`` and ``op @ X`` (k = 8), max-norm relative error ≤ 1e-5;
  * ``op.solve(b, precond="jacobi", tol=1e-6)`` converges, with a true
    residual ‖b − A·x‖/‖b‖ ≤ 1e-5 computed in float64;
  * no ``guard.*`` counter moved, the plan never degraded, and the compiled
    permuted apply contains the Pallas kernel (``tpu_custom_call``);

then repeats with ``format="auto"`` and prints the format it chose.  It
exits non-zero, and prints no result line, when JAX finds no TPU or any
check fails.  One process drives every chip; nothing runs on the CPU in the
device's place.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
GRID = 104                 # HPCG reference local grid (104³)
K = 8                      # SpMM rhs width
APPLY_RTOL = 1e-5          # max|y - y64| / max|y64| for f32 applies
SOLVE_TOL = 1e-6           # CG stopping tolerance (preconditioned loop)
RESIDUAL_TOL = 1e-5        # true float64 residual the solve must reach


class SmokeFailure(Exception):
    pass


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _rel_err(y, ref) -> float:
    import numpy as np

    y = np.asarray(y, np.float64)
    return float(np.abs(y - ref).max() / max(np.abs(ref).max(), 1e-300))


def _median_seconds(fn, *args, reps: int = 10) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def _guard_counters() -> dict:
    from repro.core import counters

    return {k: v for k, v in counters.snapshot().items()
            if k.startswith("guard.") and v}


def _true_residual(a64, x, b) -> float:
    import numpy as np

    x = np.asarray(x, np.float64)
    return float(np.linalg.norm(b - a64 @ x) / np.linalg.norm(b))


def run_single(m, a64, label: str, fmt: str, log) -> dict:
    """plan -> bind -> apply/SpMM/solve for one format; raises on failure."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.autotune import get_format

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    p = api.plan(m, execution=api.ExecutionConfig(format=fmt,
                                                  workload="solver"))
    op = p.bind(m)
    jax.block_until_ready(op.obj)
    setup_s = time.perf_counter() - t0
    log(f"{fmt}: format={p.format} partition={p.partition_strategy} "
        f"setup_s[{label}]={setup_s:.3f}")

    x = np.asarray(rng.standard_normal(m.n), np.float32)
    xd = jnp.asarray(x)
    t0 = time.perf_counter()
    y = jax.block_until_ready(op @ xd)
    first_s = time.perf_counter() - t0
    err = _rel_err(y, a64 @ x.astype(np.float64))
    apply_s = _median_seconds(op.__matmul__, xd)
    log(f"{p.format}: spmv rel_err={err:.3e} (bound {APPLY_RTOL:g}) "
        f"compile_s[{label}]={first_s:.3f} apply_median_s[{label}]="
        f"{apply_s:.6f}")
    _check(err <= APPLY_RTOL, f"{p.format} SpMV error {err:.3e}")

    X = np.asarray(rng.standard_normal((m.n, K)), np.float32)
    Xd = jnp.asarray(X)
    t0 = time.perf_counter()
    Y = jax.block_until_ready(op @ Xd)
    first_k = time.perf_counter() - t0
    err_k = _rel_err(Y, a64 @ X.astype(np.float64))
    spmm_s = _median_seconds(op.__matmul__, Xd)
    log(f"{p.format}: spmm k={K} rel_err={err_k:.3e} (bound {APPLY_RTOL:g}) "
        f"compile_s[{label}]={first_k:.3f} apply_median_s[{label}]="
        f"{spmm_s:.6f}")
    _check(err_k <= APPLY_RTOL, f"{p.format} SpMM error {err_k:.3e}")

    b = np.asarray(rng.standard_normal(m.n), np.float32)
    t0 = time.perf_counter()
    r = op.solve(jnp.asarray(b), precond="jacobi", tol=SOLVE_TOL)
    jax.block_until_ready(r.x)
    solve_s = time.perf_counter() - t0
    res = _true_residual(a64, r.x, b.astype(np.float64))
    log(f"{p.format}: cg converged={bool(r.converged)} iters={int(r.iters)} "
        f"true_residual={res:.3e} (bound {RESIDUAL_TOL:g}) "
        f"solve_s_incl_compile[{label}]={solve_s:.3f}")
    _check(bool(r.converged), f"{p.format} CG did not converge")
    _check(res <= RESIDUAL_TOL, f"{p.format} true residual {res:.3e}")

    if get_format(p.format).kernel == "pallas":
        xp = op.to_space(xd)
        hlo = jax.jit(lambda o, v: op.raw_apply_permuted(o, v)).lower(
            op.obj, xp).compile().as_text()
        _check("tpu_custom_call" in hlo,
               f"{p.format} permuted apply compiled without the Pallas "
               f"kernel")
        log(f"{p.format}: permuted apply HLO holds tpu_custom_call")
    _check(not p.degraded, f"{p.format} plan degraded: {p.degraded}")
    bad = _guard_counters()
    _check(not bad, f"guard counters moved: {bad}")
    return {"format": p.format, "iters": int(r.iters), "residual": res,
            "x": np.asarray(r.x, np.float64)}


def run_sharded(m, a64, label: str, n_dev: int, log) -> None:
    """The sharded plan over ``n_dev`` chips against the one-device solve."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.compat import make_mesh

    _check(len(jax.devices()) >= n_dev,
           f"need {n_dev} devices, found {len(jax.devices())}")
    rng = np.random.default_rng(1)
    mesh = make_mesh((n_dev,), ("data",))
    t0 = time.perf_counter()
    p = api.plan(m, mesh=mesh)
    op = p.bind(m)
    sop = p._template_for(jnp.float32)
    jax.block_until_ready(op.obj)
    setup_s = time.perf_counter() - t0
    devices = set()
    for leaf in jax.tree_util.tree_leaves(op.obj):
        held = {s.device for s in leaf.addressable_shards}
        _check(len(held) == n_dev,
               f"a sharded table sits on {len(held)} device(s), "
               f"not {n_dev}")
        devices |= held
    _check(len(devices) == n_dev, f"shards on {len(devices)} devices")
    log(f"sharded: format={p.format} partition={p.partition_strategy} "
        f"devices={sorted(d.id for d in devices)} halo_words="
        f"{sop.plan.halo_words} setup_s[{label}]={setup_s:.3f}")

    x = np.asarray(rng.standard_normal(m.n), np.float32)
    xd = jnp.asarray(x)
    t0 = time.perf_counter()
    y = jax.block_until_ready(op @ xd)
    first_s = time.perf_counter() - t0
    err = _rel_err(y, a64 @ x.astype(np.float64))
    apply_s = _median_seconds(op.__matmul__, xd)
    log(f"sharded: spmv rel_err={err:.3e} (bound {APPLY_RTOL:g}) "
        f"compile_s[{label}]={first_s:.3f} apply_median_s[{label}]="
        f"{apply_s:.6f}")
    _check(err <= APPLY_RTOL, f"sharded SpMV error {err:.3e}")

    b = np.asarray(rng.standard_normal(m.n), np.float32)
    t0 = time.perf_counter()
    r = op.solve(jnp.asarray(b), precond="jacobi", tol=SOLVE_TOL)
    jax.block_until_ready(r.x)
    solve_s = time.perf_counter() - t0
    res = _true_residual(a64, r.x, b.astype(np.float64))
    log(f"sharded: cg converged={bool(r.converged)} iters={int(r.iters)} "
        f"true_residual={res:.3e} solve_s_incl_compile[{label}]="
        f"{solve_s:.3f}")
    _check(bool(r.converged), "sharded CG did not converge")
    _check(res <= RESIDUAL_TOL, f"sharded true residual {res:.3e}")

    # same partitioning as the sharded plan: the comparison isolates the
    # sharding (and skips a second partition-strategy search on the host)
    one = api.plan(m, execution=api.ExecutionConfig(
        format="ehyb_packed", workload="solver",
        partition_method=p.partition_strategy)).bind(m)
    r1 = one.solve(jnp.asarray(b), precond="jacobi", tol=SOLVE_TOL)
    res1 = _true_residual(a64, r1.x, b.astype(np.float64))
    log(f"one-device: cg iters={int(r1.iters)} true_residual={res1:.3e}")
    _check(abs(int(r.iters) - int(r1.iters)) <= 1,
           f"sharded iters {int(r.iters)} vs one-device {int(r1.iters)}")
    _check(res1 <= RESIDUAL_TOL, f"one-device true residual {res1:.3e}")
    bad = _guard_counters()
    _check(not bad, f"guard counters moved: {bad}")


def hpcg_matrix(grid: int = GRID):
    """HPCG's 27-point operator on a grid³ mesh, symmetric positive
    definite, plus its float64 host CSR (the reference)."""
    import scipy.sparse as sp

    from repro.core.matrices import poisson3d27, symmetrize

    m = symmetrize(poisson3d27(grid))
    return m, sp.csr_matrix((m.data, m.indices, m.indptr), shape=(m.n, m.n))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path across 4 chips")
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, flush=True)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: FAIL: the repro package is not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    count = len(jax.devices())
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2
    label = f"{dev.platform} {dev.device_kind} x{count}"
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={count}")
    log(f"compile cache: {enable_compile_cache()}")

    try:
        t0 = time.perf_counter()
        m, a64 = hpcg_matrix()
        log(f"matrix: symmetrize(poisson3d27({GRID})) n={m.n} nnz={m.nnz} "
            f"build_s[host]={time.perf_counter() - t0:.3f}")
        if args.chips == 4:
            run_sharded(m, a64, label, 4, log)
        else:
            run_single(m, a64, label, "ehyb_packed", log)
            auto = run_single(m, a64, label, "auto", log)
            log(f"auto: the chip run chose format={auto['format']}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
