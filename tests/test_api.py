"""Operator API v2: plan → bind → apply lifecycle, pytree/jit stability,
and differentiability (ISSUE 5 acceptance criteria).

Covers:
* ``plan``/``PlanCache``: pattern-keyed memoization, one visible cache;
* ``Plan.bind``: host refill fast path (zero structural work, zero
  recompilation) and traced in-graph binds;
* ``LinearOperator``: pytree flatten/unflatten round trip, stable treedefs
  across binds, ``Space`` conversions, batched apply, vmap;
* ``custom_vjp``: ``grad`` of ``x ↦ (A @ x) · v`` and of values through
  ``Plan.bind(values)`` against dense autodiff, on stencil and power-law
  matrices, for both local and sharded plans;
* ``solve`` on the operator (including the distributed engine) and the
  fixed-mask value-training step;
* deprecation hygiene: the legacy entry points warn, internal code does not.
"""

import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.compat import make_mesh
from repro.core import counters, poisson3d, powerlaw
from repro.core.matrices import SparseCSR


def _mat(kind: str) -> SparseCSR:
    return poisson3d(8) if kind == "stencil" else powerlaw(256, 6)


def _with_values(m: SparseCSR, scale: float) -> SparseCSR:
    return SparseCSR(m.n, m.indptr, m.indices, m.data * scale)


def _dense_ref(m: SparseCSR):
    return m.to_dense()


STRUCTURE_COUNTERS = ("partition", "build_ehyb", "pack_staircase",
                      "build_buckets", "group_er", "build_halo_plan",
                      "shard_operator")


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["stencil", "powerlaw"])
def test_plan_bind_apply_matches_reference(kind, rng):
    m = _mat(kind)
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    ref = m.spmv(np.asarray(x, np.float64))
    scale = np.abs(ref).max()
    for fmt in ("auto", "ehyb", "ehyb_packed"):
        p = api.plan(m, execution=api.ExecutionConfig(format=fmt))
        op = p.bind(m)
        y = np.asarray(op @ x, np.float64)
        np.testing.assert_allclose(y / scale, ref / scale,
                                   rtol=5e-6, atol=5e-6)
        # batched apply
        X = jnp.asarray(rng.standard_normal((m.n, 4)), jnp.float32)
        Y = np.asarray(op @ X, np.float64)
        refX = _dense_ref(m) @ np.asarray(X, np.float64)
        np.testing.assert_allclose(Y / scale, refX / scale,
                                   rtol=5e-6, atol=5e-6)


def test_plan_cache_is_the_visible_memo():
    m = poisson3d(6)
    p1 = api.plan(m)
    p2 = api.plan(m)
    assert p1 is p2, "same pattern + execution must resolve to one Plan"
    assert api.PLAN_CACHE.stats()["plans"] >= 1
    # a different execution config is a different plan
    p3 = api.plan(m, execution=api.ExecutionConfig(workload="solver"))
    assert p3 is not p1
    # the old module-level globals are gone for good
    import repro.autotune.registry as reg
    import repro.core.spmv as spmv_mod

    for name in ("_OP_CACHE", "_OP_PATTERN_CACHE"):
        assert not hasattr(spmv_mod, name)
    for name in ("_HOST_EHYB", "_HOST_EHYB_PATTERN"):
        assert not hasattr(reg, name)


def test_rebind_is_refill_only():
    m1 = poisson3d(6)
    m2 = _with_values(m1, 2.5)
    p = api.plan(m1, execution=api.ExecutionConfig(format="ehyb"))
    op1 = p.bind(m1)
    before = counters.snapshot()
    op2 = p.bind(m2)
    after = counters.snapshot()
    work = {k: after.get(k, 0) - before.get(k, 0)
            for k in STRUCTURE_COUNTERS
            if after.get(k, 0) != before.get(k, 0)}
    assert work == {}, f"rebind must not redo structural work: {work}"
    # structural arrays shared by reference, value tables fresh
    assert op2.obj.perm is op1.obj.perm
    assert op2.obj.ell_vals is not op1.obj.ell_vals
    x = jnp.ones(m1.n, jnp.float32)
    np.testing.assert_allclose(np.asarray(op2 @ x), 2.5 * np.asarray(op1 @ x),
                               rtol=1e-5, atol=1e-5)


def test_update_values_and_exact_rebind_identity():
    m1 = poisson3d(6)
    p = api.plan(m1, execution=api.ExecutionConfig(format="ehyb"))
    op1 = p.bind(m1)
    op2 = p.bind(m1)              # exact value hit: same container
    assert op2.obj is op1.obj
    op3 = op1.update_values(_with_values(m1, 3.0))
    assert op3.plan is p and op3.obj.perm is op1.obj.perm


def test_update_values_rejects_unknown_kwargs():
    """Regression: ``update_values`` used to take ``**_ignored``, so a
    typo'd keyword (``dytpe=...``) was silently swallowed and the caller's
    intent dropped on the floor.  It must raise a TypeError naming the
    stray argument."""
    m = poisson3d(6)
    op = api.plan(m).bind(m)
    with pytest.raises(TypeError, match="dytpe"):
        op.update_values(_with_values(m, 2.0), dytpe=jnp.float64)
    op2 = op.update_values(_with_values(m, 2.0))     # positional path intact
    assert op2.plan is op.plan


# ---------------------------------------------------------------------------
# pytree + jit-cache stability
# ---------------------------------------------------------------------------

def test_pytree_flatten_unflatten_roundtrip(rng):
    m = poisson3d(6)
    p = api.plan(m, execution=api.ExecutionConfig(format="ehyb"))
    op = p.bind(m)
    leaves, treedef = jax.tree_util.tree_flatten(op)
    op_rt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(op_rt, api.LinearOperator)
    assert op_rt.plan is p
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    np.testing.assert_array_equal(np.asarray(op_rt @ x), np.asarray(op @ x))


def test_bind_with_new_values_triggers_zero_recompilation():
    m1 = poisson3d(6)
    m2 = _with_values(m1, 1.7)
    p = api.plan(m1, execution=api.ExecutionConfig(format="ehyb"))
    op1 = p.bind(m1)
    x = jnp.ones(m1.n, jnp.float32)
    # warm both dispatch paths: the eager engine apply and the
    # differentiable custom-vjp wrapper
    jax.block_until_ready(op1 @ x)
    jax.block_until_ready(op1._diff_apply()(op1.obj, x))
    probes = [getattr(p._raw_apply(), "_cache_size", None),
              getattr(op1._diff_apply(), "_cache_size", None)]
    if any(pr is None for pr in probes):
        pytest.skip("jit cache-size probe unavailable on this jax")
    # treedefs identical across binds (the aux is the Plan itself)
    t1 = jax.tree_util.tree_flatten(op1)[1]
    op2 = p.bind(m2)
    t2 = jax.tree_util.tree_flatten(op2)[1]
    assert t1 == t2
    n0 = [pr() for pr in probes]
    jax.block_until_ready(op2 @ x)
    jax.block_until_ready(op2._diff_apply()(op2.obj, x))
    assert [pr() for pr in probes] == n0, \
        "rebinding values must hit the existing jit caches"


def test_operator_passes_through_jit_boundary(rng):
    m = poisson3d(6)
    p = api.plan(m, execution=api.ExecutionConfig(format="ehyb"))
    op = p.bind(m)
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)

    @jax.jit
    def f(o, xx):
        return o @ xx

    np.testing.assert_allclose(np.asarray(f(op, x)), np.asarray(op @ x),
                               rtol=1e-6, atol=1e-6)


def test_vmap_over_rhs(rng):
    m = poisson3d(6)
    op = api.plan(m).bind(m)
    X = jnp.asarray(rng.standard_normal((3, m.n)), jnp.float32)
    Y = jax.vmap(lambda xx: op @ xx)(X)
    ref = np.asarray(X, np.float64) @ _dense_ref(m).T
    np.testing.assert_allclose(np.asarray(Y, np.float64), ref,
                               rtol=5e-5, atol=5e-5)


# ---------------------------------------------------------------------------
# spaces
# ---------------------------------------------------------------------------

def test_space_enum_roundtrip_and_permuted_apply(rng):
    m = poisson3d(6)
    op = api.plan(m, execution=api.ExecutionConfig(format="ehyb")).bind(m)
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    xp = op.to_space(x, api.Space.PERMUTED)
    assert xp.shape == (op.n_pad,)
    np.testing.assert_allclose(
        np.asarray(op.from_space(xp, api.Space.PERMUTED)), np.asarray(x),
        rtol=0, atol=0)
    y_perm = op.from_space(op.apply(xp, space=api.Space.PERMUTED))
    np.testing.assert_allclose(np.asarray(y_perm), np.asarray(op @ x),
                               rtol=1e-5, atol=1e-5)
    # ORIGINAL is the identity space
    np.testing.assert_array_equal(
        np.asarray(op.to_space(x, api.Space.ORIGINAL)), np.asarray(x))
    with pytest.raises(ValueError):
        api.plan(m, execution=api.ExecutionConfig(format="csr")) \
           .bind(m).to_space(x, api.Space.PERMUTED)


# ---------------------------------------------------------------------------
# differentiation (acceptance: 1e-5 fp32, stencil + powerlaw, local+sharded)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["stencil", "powerlaw"])
@pytest.mark.parametrize("sharded", [False, True])
def test_grad_through_apply_matches_dense(kind, sharded, rng):
    m = _mat(kind)
    mesh = make_mesh((1,), ("data",)) if sharded else None
    p = api.plan(m, mesh=mesh)
    op = p.bind(m)
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    v = jnp.asarray(rng.standard_normal(m.n), jnp.float32)

    g = jax.grad(lambda xx: jnp.vdot(op @ xx, v))(x)
    g_ref = _dense_ref(m).T @ np.asarray(v, np.float64)
    scale = max(np.abs(g_ref).max(), 1e-12)
    np.testing.assert_allclose(np.asarray(g, np.float64) / scale,
                               g_ref / scale, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["stencil", "powerlaw"])
@pytest.mark.parametrize("sharded", [False, True])
def test_grad_through_bound_values_matches_dense(kind, sharded, rng):
    m = _mat(kind)
    mesh = make_mesh((1,), ("data",)) if sharded else None
    p = api.plan(m, mesh=mesh)
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    v = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    vals = jnp.asarray(m.data, jnp.float32)

    gv = jax.grad(lambda vv: jnp.vdot(p.bind(vv) @ x, v))(vals)
    rows = np.repeat(np.arange(m.n), m.row_lengths())
    gv_ref = (np.asarray(v, np.float64)[rows]
              * np.asarray(x, np.float64)[m.indices])
    scale = max(np.abs(gv_ref).max(), 1e-12)
    np.testing.assert_allclose(np.asarray(gv, np.float64) / scale,
                               gv_ref / scale, rtol=1e-5, atol=1e-5)


def test_grad_all_formats_no_double_counting(rng):
    """ER values are stored twice in some containers (global + fused
    tiles); the cotangent must flow through exactly one copy."""
    m = powerlaw(192, 6)
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    v = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    vals = jnp.asarray(m.data, jnp.float32)
    rows = np.repeat(np.arange(m.n), m.row_lengths())
    gv_ref = (np.asarray(v, np.float64)[rows]
              * np.asarray(x, np.float64)[m.indices])
    scale = max(np.abs(gv_ref).max(), 1e-12)
    for fmt in ("csr", "ell", "hyb", "ehyb", "ehyb_bucketed",
                "ehyb_packed", "dense"):
        p = api.plan(m, execution=api.ExecutionConfig(format=fmt))
        gv = jax.grad(lambda vv: jnp.vdot(p.bind(vv) @ x, v))(vals)
        np.testing.assert_allclose(
            np.asarray(gv, np.float64) / scale, gv_ref / scale,
            rtol=1e-5, atol=1e-5, err_msg=f"format {fmt}")


def test_grad_fp64_cotangent_not_downcast(rng):
    """An fp64 cotangent must flow through Aᵀḡ at fp64.

    Regression: the local VJP branch bound the transpose plan at the stored
    values' dtype and cast ``g.astype(vals.dtype)`` — rounding an fp64
    cotangent to fp32 (~1e-7 relative) before the transpose apply.  With
    the transpose bound at the promoted accumulation dtype the gradient
    agrees with the dense reference (built from the same fp32-rounded
    values, so storage rounding can't mask the bug) to fp64 resolution."""
    m = poisson3d(6)
    m32 = SparseCSR(m.n, m.indptr, m.indices,
                    m.data.astype(np.float32).astype(np.float64))
    dense = m32.to_dense()                          # fp64, fp32-rounded vals
    with jax.enable_x64():
        op = api.plan(m).bind(m)                    # values stored at fp32
        x = jnp.asarray(rng.standard_normal(m.n), jnp.float64)
        v = jnp.asarray(rng.standard_normal(m.n), jnp.float64)
        g = jax.grad(lambda xx: jnp.vdot(op @ xx, v))(x)
        assert g.dtype == jnp.float64
        g = np.asarray(g)
    g_ref = dense.T @ np.asarray(v, np.float64)
    err = np.abs(g - g_ref).max() / max(np.abs(g_ref).max(), 1e-12)
    assert err < 1e-10, f"fp64 cotangent was downcast (rel err {err:.2e})"


def test_transpose_operator(rng):
    m = powerlaw(128, 5)
    op = api.plan(m).bind(m)
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    yt = np.asarray(op.T @ x, np.float64)
    ref = _dense_ref(m).T @ np.asarray(x, np.float64)
    scale = max(np.abs(ref).max(), 1e-12)
    np.testing.assert_allclose(yt / scale, ref / scale, rtol=5e-6, atol=5e-6)


# ---------------------------------------------------------------------------
# solve through the operator
# ---------------------------------------------------------------------------

def test_operator_solve_matches_legacy_and_distributed(rng):
    m = poisson3d(6)
    b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    p = api.plan(m, execution=api.ExecutionConfig(workload="solver"))
    op = p.bind(m)
    r = op.solve(b, tol=1e-8, max_iters=400)
    assert bool(r.converged)
    x_ref = np.linalg.solve(_dense_ref(m), np.asarray(b, np.float64))
    np.testing.assert_allclose(np.asarray(r.x, np.float64), x_ref,
                               rtol=5e-4, atol=5e-4)
    # the sharded plan solves through the same method
    mesh = make_mesh((1,), ("data",))
    opd = api.plan(m, mesh=mesh).bind(m)
    rd = opd.solve(b, tol=1e-8, max_iters=400)
    assert bool(rd.converged)
    np.testing.assert_allclose(np.asarray(rd.x, np.float64), x_ref,
                               rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# fixed-mask value training (train-layer consumer)
# ---------------------------------------------------------------------------

def test_sparse_value_train_step_reduces_loss(rng):
    from repro.train.optimizer import (OptimizerConfig, init_opt_state)
    from repro.train.train_step import make_sparse_value_train_step

    m = poisson3d(5)
    p = api.plan(m, execution=api.ExecutionConfig(format="ehyb"))
    x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    y_target = jnp.asarray(rng.standard_normal(m.n), jnp.float32)

    def loss_fn(op):
        d = op @ x - y_target
        return jnp.vdot(d, d).real / m.n

    opt_cfg = OptimizerConfig(lr=0.3, warmup_steps=0, weight_decay=0.0,
                              clip_norm=1e9)
    values = jnp.asarray(m.data, jnp.float32)
    opt = init_opt_state({"values": values})
    step = make_sparse_value_train_step(p, loss_fn, opt_cfg)
    losses = []
    for _ in range(25):
        values, opt, metrics = step(values, opt)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < 0.2 * losses[0], losses


# ---------------------------------------------------------------------------
# deprecation hygiene
# ---------------------------------------------------------------------------

def test_legacy_entry_points_emit_deprecation_warnings(rng):
    from repro.core import build_spmv, solve, spmv
    from repro.core.sparse_linear import SparseLinear
    from repro.dist import build_sharded_spmv

    m = poisson3d(5)
    x = jnp.ones(m.n, jnp.float32)
    with pytest.warns(DeprecationWarning, match="spmv is deprecated"):
        spmv(m, x)
    with pytest.warns(DeprecationWarning, match="build_spmv is deprecated"):
        build_spmv(m, "csr")
    with pytest.warns(DeprecationWarning, match="solve is deprecated"):
        solve(m, x, max_iters=3)
    with pytest.warns(DeprecationWarning, match="from_dense is deprecated"):
        SparseLinear.from_dense(np.asarray(
            np.random.default_rng(0).standard_normal((16, 16))), 0.3)
    mesh = make_mesh((1,), ("data",))
    with pytest.warns(DeprecationWarning,
                      match="build_sharded_spmv is deprecated"):
        build_sharded_spmv(m, mesh, "data", format="ehyb")


def test_internal_code_calls_no_deprecated_entry_points(rng):
    """Errors any DeprecationWarning attributed to a repro.* caller — the
    shims warn with stacklevel=2, so a warning lands on repro code exactly
    when internal code calls a deprecated entry point."""
    m = poisson3d(5)
    b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
    with warnings.catch_warnings():
        warnings.filterwarnings("error", category=DeprecationWarning,
                                module=r"repro\.")
        p = api.plan(m, execution=api.ExecutionConfig(workload="solver"))
        op = p.bind(m)
        op = op.update_values(_with_values(m, 1.5))
        op.solve(b, max_iters=50)
        jax.grad(lambda xx: (op @ xx).sum())(b)
        layer = api.pruned_linear(
            np.asarray(rng.standard_normal((24, 32))), density=0.3)
        layer = layer.update_values(
            np.asarray(rng.standard_normal((24, 32))))
        layer(jnp.ones((2, 32), jnp.float32))
        mesh = make_mesh((1,), ("data",))
        opd = api.plan(m, mesh=mesh).bind(m)
        opd.solve(b, max_iters=50)


# ---------------------------------------------------------------------------
# multi-device sharded grads (subprocess; mirrors test_dist's harness)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_multi_device_sharded_grads():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro import api
        from repro.compat import make_mesh
        from repro.core import poisson3d, powerlaw

        out = {}
        rng = np.random.default_rng(0)
        for name, m in (("stencil", poisson3d(10)),
                        ("powerlaw", powerlaw(1024, 6))):
            mesh = make_mesh((8,), ("data",))
            p = api.plan(m, mesh=mesh)
            op = p.bind(m)
            x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
            v = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
            g = jax.grad(lambda xx: jnp.vdot(op @ xx, v))(x)
            ad = m.to_dense()
            g_ref = ad.T @ np.asarray(v, np.float64)
            s = max(np.abs(g_ref).max(), 1e-12)
            out[name + "/gx"] = float(
                np.abs(np.asarray(g, np.float64) - g_ref).max() / s)
            vals = jnp.asarray(m.data, jnp.float32)
            gv = jax.grad(lambda vv: jnp.vdot(p.bind(vv) @ x, v))(vals)
            rows = np.repeat(np.arange(m.n), m.row_lengths())
            gv_ref = (np.asarray(v, np.float64)[rows]
                      * np.asarray(x, np.float64)[m.indices])
            sv = max(np.abs(gv_ref).max(), 1e-12)
            out[name + "/gv"] = float(
                np.abs(np.asarray(gv, np.float64) - gv_ref).max() / sv)
        print(json.dumps(out))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    import json

    out = json.loads(res.stdout.strip().splitlines()[-1])
    for k, err in out.items():
        assert err < 1e-5, (k, err, out)
