"""Sharded EHYB operator tests.

Host-level: HaloPlan invariants and a full numpy simulation of the exchange
(send/push/all_to_all/recv replayed with plain arrays against the CSR
reference — no mesh needed), the partition-padding and dtype-promotion
regressions, the interconnect-aware cost model, and refill counters.

Multi-device: one subprocess with 8 virtual host devices sweeps
dist-vs-local equivalence (original/permuted spaces, batched rhs, fp64,
refill-then-apply) plus distributed-vs-local ``solve()`` and the measured
collective-bytes ratio of the halo exchange against the legacy all-gather
path.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import build_ehyb, build_spmv, poisson3d, powerlaw, spmv
from repro.core.counters import COUNTERS, reset
from repro.core.matrices import SparseCSR
from repro.dist import build_halo_plan, build_sharded_spmv, ehyb_halo_words


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# host-level: plan invariants + numpy simulation of the exchange
# ---------------------------------------------------------------------------

def simulate_plan(e, plan, x_new: np.ndarray) -> np.ndarray:
    """Replay the sharded apply with plain numpy: per-device ELL, the
    send/push buffer, the all_to_all transpose, the halo gather, the
    compact-column ER einsum, and the partial-y scatter."""
    L, nd, S = plan.local_size, plan.n_dev, plan.seg_len
    N = plan.n_pad_dist
    x = np.zeros(N)
    x[: e.n_pad] = x_new
    fer_vals = plan.fill_fetch(e.er_vals)
    pe_vals = plan.fill_push(e.er_vals)
    y = np.zeros(N)
    # ELL: partition-local compact gather
    P_, V = e.n_parts, e.vec_size
    base = (np.arange(P_) * V)[:, None, None]
    g = x[base + e.ell_cols.astype(np.int64)]
    y[: P_ * V] = np.einsum("pvw,pvw->pv", e.ell_vals, g).reshape(-1)
    if not plan.has_er:
        return y
    # exchange buffer: fetch gathers + push partials
    buf = np.zeros((nd, nd, S))
    for s in range(nd):
        buf[s] = x[s * L + plan.send_idx[s]] * plan.send_mask[s]
        contrib = pe_vals[s] * x[s * L + plan.pe_cols[s]] * plan.pe_mask[s]
        np.add.at(buf[s].reshape(-1), plan.pe_dst[s], contrib)
    for d in range(nd):
        recv = buf[:, d].reshape(-1)           # all_to_all: segment d of all
        x_ext = np.concatenate([x[d * L: (d + 1) * L], recv[plan.recv_sel[d]]])
        ye = np.einsum("ew,ew->e", fer_vals[d], x_ext[plan.fer_cols[d]])
        np.add.at(y, d * L + plan.fer_rows[d], ye)
        part = recv[plan.rp_sel[d]] * plan.rp_mask[d]
        np.add.at(y, d * L + plan.rp_rows[d], part)
    return y


def reference_permuted(m, e, plan, x_new: np.ndarray) -> np.ndarray:
    x_o = x_new[np.asarray(e.inv_perm[: m.n])]
    y_o = m.spmv(x_o)
    y_ref = np.zeros(plan.n_pad_dist)
    live = e.perm < m.n
    y_ref[: e.n_pad][live] = y_o[e.perm[live]]
    return y_ref


@pytest.mark.parametrize("mat,n_dev", [("poisson", 4), ("poisson", 8),
                                       ("powerlaw", 4), ("powerlaw", 8)])
def test_halo_plan_numpy_simulation(mat, n_dev, rng):
    """The planned exchange, replayed in numpy, reproduces A@x exactly —
    including the y-push direction powerlaw matrices trigger."""
    m = poisson3d(10) if mat == "poisson" else powerlaw(1024, 6, seed=7)
    e = build_ehyb(m)
    plan = build_halo_plan(e, n_dev)
    x_new = np.zeros(e.n_pad)
    x_new[:] = 0.0
    x_o = rng.standard_normal(m.n)
    x_new[np.asarray(e.inv_perm[: m.n])] = x_o
    y = simulate_plan(e, plan, x_new)
    y_ref = reference_permuted(m, e, plan, x_new)
    np.testing.assert_allclose(y, y_ref, rtol=1e-10, atol=1e-10)
    if mat == "powerlaw":
        assert plan.has_push            # the adaptive direction really fires
    assert plan.halo_words < plan.allgather_words
    assert plan.halo_words == int(plan.counts_fetch.sum()
                                  + plan.counts_push.sum())
    assert ehyb_halo_words(e, n_dev) == plan.halo_words


def test_halo_plan_partition_padding(rng):
    """Regression: n_parts % n_dev != 0 pads with empty partitions instead
    of raising (historically a ValueError)."""
    m = poisson3d(9)
    e = build_ehyb(m, n_parts=3, vec_size=-(-m.n // 3 // 8) * 8)
    plan = build_halo_plan(e, 2)
    assert plan.n_parts_pad == 4 and plan.parts_per_dev == 2
    assert plan.n_pad_dist == 4 * e.vec_size > e.n_pad
    x_new = np.zeros(e.n_pad)
    x_new[np.asarray(e.inv_perm[: m.n])] = rng.standard_normal(m.n)
    np.testing.assert_allclose(simulate_plan(e, plan, x_new),
                               reference_permuted(m, e, plan, x_new),
                               rtol=1e-10, atol=1e-10)


def test_sharded_dtype_promotion(rng):
    """Regression: the sharded apply promotes a non-float rhs to the value
    dtype exactly like ``spmv()`` (an int rhs must not run integer math)."""
    m = poisson3d(8)
    mesh = make_mesh((1,), ("data",))
    sop = build_sharded_spmv(m, mesh, "data", format="ehyb")
    xi = jnp.arange(m.n, dtype=jnp.int32)
    yi = sop(xi)
    assert jnp.issubdtype(yi.dtype, jnp.floating)
    np.testing.assert_allclose(np.asarray(yi), np.asarray(spmv(m, xi)),
                               rtol=1e-5, atol=1e-5)
    # permuted entry point promotes too
    yp = sop.from_permuted(sop.matvec_permuted(sop.to_permuted(xi)))
    assert jnp.issubdtype(yp.dtype, jnp.floating)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yi),
                               rtol=1e-5, atol=1e-5)


def test_dist_spmv_shim_exports_are_audited():
    """ISSUE 5 satellite: the shim must only (re-)export names that still
    resolve — importing it and touching ``__all__`` under error-level
    warning filters must not raise, forwarded names must exist in
    ``repro.dist`` (with a DeprecationWarning on access), and stale names
    must fail fast with AttributeError."""
    import importlib
    import warnings

    import repro.dist as dist

    with warnings.catch_warnings():
        # strict import: the shim itself must not warn at import time
        # (-W error::FutureWarning-safe: error on every warning category)
        warnings.simplefilter("error")
        mod = importlib.reload(importlib.import_module("repro.core.dist_spmv"))
        for name in mod.__all__:
            assert getattr(mod, name) is not None
    for name in mod._FORWARDED:
        assert hasattr(dist, name), f"stale forwarded export {name!r}"
        with pytest.warns(DeprecationWarning, match=name):
            assert getattr(mod, name) is getattr(dist, name)
    with pytest.raises(AttributeError):
        mod.all_gather_spmv          # the pre-halo API: pruned, stays gone


def test_dist_spmv_shim_deprecated(rng):
    """core.dist_spmv survives as a warning shim over repro.dist."""
    from repro.core.dist_spmv import build_dist_spmv

    m = poisson3d(8)
    op = build_spmv(m, format="ehyb")
    mesh = make_mesh((1,), ("data",))
    with pytest.deprecated_call():
        dist = build_dist_spmv(op, mesh, "data")
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    np.testing.assert_allclose(np.asarray(dist(x)), np.asarray(op(x)),
                               rtol=1e-5, atol=1e-5)


def test_dist_cost_model_interconnect():
    """context="dist" = solver-context HBM bytes + the interconnect term:
    halo words for shardable formats, the all-gather penalty otherwise."""
    from repro import autotune as at

    m = poisson3d(12)
    shared = {}
    solver_b = at.estimate_bytes(m, "ehyb", 4, shared, context="solver")
    dist_b = at.estimate_bytes(m, "ehyb", 4, dict(shared, n_dev=4),
                               context="dist")
    e = at.registry.shared_ehyb(m, shared)
    assert dist_b == solver_b + 4 * ehyb_halo_words(e, 4)
    csr_solver = at.estimate_bytes(m, "csr", 4, shared, context="solver")
    csr_dist = at.estimate_bytes(m, "csr", 4, dict(shared, n_dev=4),
                                 context="dist")
    assert csr_dist == csr_solver + at.allgather_penalty_bytes(m.n, 4, 4)
    # a stencil favors EHYB even harder once interconnect is priced in
    table = at.model_table(m, 4, shared={"n_dev": 4}, context="dist")
    assert table["ehyb"] < table["csr"]
    shardable = tuple(f for f in at.available_formats()
                      if at.get_format(f).shard is not None)
    r = at.autotune(m, context="dist", n_dev=4, candidates=shardable)
    assert r.format in shardable
    with pytest.raises(ValueError):
        at.autotune(m, context="nonsense")


def test_sharded_refill_counters(rng):
    """update_values on a sharded operator: one value scatter, zero
    partitioning, zero halo re-planning, and the jitted applies are
    reused (same pytree structure)."""
    m = poisson3d(8)
    mesh = make_mesh((1,), ("data",))
    sop = build_sharded_spmv(m, mesh, "data", format="ehyb")
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    y1 = np.asarray(sop(x))
    m2 = SparseCSR(m.n, m.indptr, m.indices, m.data * 2.5)
    reset()
    sop2 = sop.update_values(m2)
    snap = dict(COUNTERS)
    assert snap.get("ehyb_refill") == 1
    for structural in ("build_ehyb", "build_halo_plan", "group_er",
                       "pack_staircase", "build_buckets", "shard_operator"):
        assert snap.get(structural, 0) == 0, snap
    assert sop2.apply is sop.apply                # same jitted closures
    assert sop2.apply_permuted is sop.apply_permuted
    np.testing.assert_allclose(np.asarray(sop2(x)), 2.5 * y1,
                               rtol=1e-5, atol=1e-4)
    with pytest.raises(ValueError):
        sop.update_values(poisson3d(7))           # different pattern


def test_build_sharded_rejects_unshardable_format():
    m = poisson3d(8)
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="no partition structure"):
        build_sharded_spmv(m, mesh, "data", format="csr")


def test_serve_sparse_head_mesh():
    """ServeEngine accepts a mesh for the pruned decode head (plumbing
    smoke on a degenerate 1-device mesh; the sharded math is pinned by the
    equivalence sweep)."""
    from repro.configs import get_config
    from repro.models import init_model
    from repro.serve import Request, ServeEngine

    cfg = get_config("llama3_2_1b", smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((1,), ("data",))
    prompt = np.arange(1, 7, dtype=np.int32)
    outs = []
    for kw in ({}, {"sparse_head_mesh": mesh}):
        eng = ServeEngine(params, cfg, batch=1, max_len=32, max_prompt=8,
                          sparse_head_density=0.9, **kw)
        eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
        outs.append(eng.run_until_done()[0].generated)
    assert outs[0] == outs[1]
    # API v2: the head is a LinearOperator whose plan is sharded — the
    # ShardedOperator is the engine behind it, not a parallel API
    op = eng.sparse_head.op
    assert op.plan.is_sharded and op.plan.mesh is mesh
    from repro.dist import EHYBShards
    assert isinstance(op.obj, EHYBShards)


# ---------------------------------------------------------------------------
# multi-device: equivalence sweep + distributed solve + measured collectives
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_dist_equivalence_sweep():
    out = run_with_devices("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh
        from repro.core import build_ehyb, build_spmv, poisson3d, powerlaw, solve
        from repro.core.counters import COUNTERS, reset
        from repro.core.matrices import SparseCSR
        from repro.dist import build_allgather_spmv, build_sharded_spmv
        from repro.roofline.hlo_cost import analyze_hlo

        res = {}
        rng = np.random.default_rng(0)
        for name, m, ndv in (("poisson", poisson3d(12), 8),
                             ("powerlaw", powerlaw(2048, 6), 8)):
            mesh = make_mesh((ndv,), ("data",))
            op = build_spmv(m, format="ehyb")
            sop = build_sharded_spmv(m, mesh, "data", format="ehyb")
            x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
            X = jnp.asarray(rng.standard_normal((m.n, 3)), jnp.float32)
            res[name + "/orig"] = float(jnp.abs(sop(x) - op(x)).max())
            res[name + "/batched"] = float(jnp.abs(sop(X) - op(X)).max())
            xn = sop.to_permuted(x)
            res[name + "/permuted"] = float(jnp.abs(
                sop.from_permuted(sop.matvec_permuted(xn)) - op(x)).max())
            # refill-then-apply: pattern fixed, values pushed, zero re-partitioning
            m2 = SparseCSR(m.n, m.indptr, m.indices, m.data * 1.5)
            reset()
            sop2 = sop.update_values(m2)
            snap = dict(COUNTERS)
            res[name + "/refill_structural"] = sum(
                snap.get(k, 0) for k in ("build_ehyb", "build_halo_plan",
                                         "group_er", "pack_staircase"))
            res[name + "/refill_err"] = float(jnp.abs(
                sop2(x) - 1.5 * op(x)).max())
            # distributed solve vs single-device solve
            b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
            r0 = solve(m, b, precond="jacobi", format="ehyb", max_iters=250)
            r1 = solve(sop, b, precond="jacobi", max_iters=250)
            res[name + "/solve_x_err"] = float(jnp.abs(r0.x - r1.x).max())
            res[name + "/solve_res"] = [float(r0.residual), float(r1.residual)]
            res[name + "/solve_iters"] = [int(r0.iters), int(r1.iters)]
            if name == "poisson":       # bicgstab breaks down (NaN omega)
                rb = solve(m, b, method="bicgstab", precond="jacobi",
                           format="ehyb", max_iters=250)
                rb1 = solve(sop, b, method="bicgstab", precond="jacobi",
                            max_iters=250)
                res[name + "/bicg_x_err"] = float(jnp.abs(rb.x - rb1.x).max())
            # measured collective bytes: halo exchange vs legacy all-gather
            xp = sop.to_permuted(x)
            halo_hlo = jax.jit(sop.matvec_permuted).lower(xp).compile().as_text()
            legacy = build_allgather_spmv(op.obj, mesh, "data",
                                          space="permuted")
            leg_hlo = jax.jit(legacy).lower(xp).compile().as_text()
            res[name + "/coll_halo"] = analyze_hlo(halo_hlo)["coll_bytes"]
            res[name + "/coll_legacy"] = analyze_hlo(leg_hlo)["coll_bytes"]
            res[name + "/halo_words"] = sop.plan.halo_words
            res[name + "/allgather_words"] = sop.plan.allgather_words

        # fp64 equivalence
        with jax.enable_x64(True):
            m = poisson3d(10)
            mesh = make_mesh((4,), ("data",))
            sop = build_sharded_spmv(m, mesh, "data", format="ehyb",
                                     dtype=jnp.float64)
            x = jnp.asarray(rng.standard_normal(m.n))
            res["fp64/dtype"] = str(sop(x).dtype)
            res["fp64/err"] = float(np.abs(np.asarray(sop(x))
                                           - m.spmv(np.asarray(x))).max())

        # partition padding on a real mesh: n_parts=6, n_dev=4
        m = poisson3d(10)
        e = build_ehyb(m, n_parts=6, vec_size=-(-m.n // 6 // 8) * 8)
        mesh = make_mesh((4,), ("data",))
        sop = build_sharded_spmv(e, mesh, "data")
        op = build_spmv(m, format="ehyb", shared={"ehyb": e})
        x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
        res["pad/err"] = float(jnp.abs(sop(x) - op(x)).max())
        print(json.dumps(res))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    for k, v in res.items():
        if k.endswith(("err", "orig", "batched", "permuted")):
            assert v < 2e-4, (k, res)
    assert res["poisson/refill_structural"] == 0
    assert res["powerlaw/refill_structural"] == 0
    assert res["fp64/dtype"] == "float64"
    assert res["fp64/err"] < 1e-10
    for name in ("poisson", "powerlaw"):
        r0, r1 = res[name + "/solve_res"]
        assert abs(r0 - r1) < 1e-4, res
        # the acceptance ratio: scheduled halo payload under 35 % of the
        # words the all-gather implementation moves on the same matrix/mesh
        assert res[name + "/halo_words"] < 0.35 * res[name + "/allgather_words"], res
        # and the physical collective shrank too (HLO-counted bytes include
        # the all_to_all's padding and self-segment, so the bound is looser)
        assert res[name + "/coll_halo"] < 0.5 * res[name + "/coll_legacy"], res
