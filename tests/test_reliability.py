"""Reliability layer: guarded apply, solver guardrails, serving admission
control — every recovery path driven by deterministic fault injection
(``repro.reliability.chaos``), asserting both that the fault actually fired
and that the system degraded gracefully instead of crashing or silently
corrupting results."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecutionConfig, plan
from repro.core import counters
from repro.core.matrices import SparseCSR, poisson3d, unstructured
from repro.core.solver import PRECONDITIONERS, SolveResult, bicgstab, cg
from repro.reliability import (EnginePolicy, ReliabilityWarning,
                               SolveFailure, SolveFailureWarning,
                               SolvePolicy, chaos, flood)
from repro.reliability.guard import reset_warned


@pytest.fixture(autouse=True)
def _quiet_reliability_warnings():
    """These tests trigger degradations on purpose; assertions use counters
    and statuses, not warning capture (except where pytest.warns is the
    point)."""
    reset_warned()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", ReliabilityWarning)
        yield


def _dense_mv(m):
    a = jnp.asarray(m.to_dense(), jnp.float32)
    return lambda v: a @ v


# ---------------------------------------------------------------------------
# guarded apply: fallback chain + recovery
# ---------------------------------------------------------------------------

class TestGuardedApply:
    def test_native_failure_raises_on_tpu(self, rng, monkeypatch):
        """On the TPU (no chaos armed) a native Pallas failure is an error,
        never a silent reference apply under the format's name."""
        from repro.reliability import guard

        m = unstructured(256, 8, seed=31)
        p = plan(m, execution=ExecutionConfig(format="ehyb_packed"))
        op = p.bind(m)
        tpl = p._any_template()

        def broken(obj, x):
            raise RuntimeError("Mosaic refused the kernel")

        monkeypatch.setattr(tpl, "apply", broken)
        monkeypatch.setattr(guard, "_on_tpu", lambda: True)
        x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
        before = counters.snapshot()
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            op @ x
        assert p.degraded == {}
        assert counters.snapshot().get("guard.downgrade", 0) == before.get(
            "guard.downgrade", 0)

    def test_all_pallas_failure_falls_back_to_reference(self, rng):
        m = unstructured(256, 8, seed=32)
        p = plan(m, execution=ExecutionConfig(format="ehyb_packed"))
        op = p.bind(m)
        x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
        want = m.to_dense() @ np.asarray(x, np.float64)
        before = counters.snapshot()
        with chaos(kernel_failure=("ehyb_packed:*",)) as cfg:
            y = np.asarray(op @ x, np.float64)
            assert p.degraded == {"apply": "reference"}
        assert cfg.injected["kernel:ehyb_packed:native"] >= 1
        after = counters.snapshot()
        assert after.get("guard.downgrade", 0) > before.get(
            "guard.downgrade", 0)
        assert after.get("guard.downgrade.ehyb_packed", 0) > before.get(
            "guard.downgrade.ehyb_packed", 0)
        np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)

    def test_guard_recovers_native_after_chaos_exits(self, rng):
        m = unstructured(256, 8, seed=33)
        p = plan(m, execution=ExecutionConfig(format="ehyb_packed"))
        op = p.bind(m)
        x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
        with chaos(kernel_failure=("ehyb_packed:*",)):
            np.asarray(op @ x)
            assert p.degraded
        # epoch moved on exit: the next dispatch re-resolves to native
        want = m.to_dense() @ np.asarray(x, np.float64)
        y = np.asarray(op @ x, np.float64)
        assert p.degraded == {}
        np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)

    def test_guarded_solve_converges_on_reference(self, rng):
        """Tentpole acceptance: a forced Pallas lowering failure must leave
        solve() working through the fallback chain, conformant with the
        dense oracle."""
        m = poisson3d(8)
        p = plan(m, execution=ExecutionConfig(format="ehyb_packed",
                                              workload="solver"))
        op = p.bind(m)
        b = rng.standard_normal(m.n).astype(np.float32)
        with chaos(kernel_failure=("ehyb_packed:*",)) as cfg:
            r = op.solve(jnp.asarray(b), tol=1e-5)
            assert p.degraded.get("permuted") == "reference"
        assert cfg.injected
        assert r.status == "converged"
        ax = m.spmv(np.asarray(r.x, np.float64))
        assert np.linalg.norm(ax - b) / np.linalg.norm(b) < 1e-4

    def test_backend_probe_failure_disables_pallas_levels(self, rng):
        from repro.kernels.ops import backend_supports_pallas

        assert backend_supports_pallas()      # healthy CPU interpreter
        m = unstructured(128, 6, seed=34)
        p = plan(m, execution=ExecutionConfig(format="ehyb_packed"))
        op = p.bind(m)
        x = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
        want = m.to_dense() @ np.asarray(x, np.float64)
        with chaos(kernel_failure=("pallas:probe",)):
            assert not backend_supports_pallas()
            y = np.asarray(op @ x, np.float64)
            assert p.degraded == {"apply": "reference"}
        np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
        assert backend_supports_pallas()      # re-probed after the epoch


# ---------------------------------------------------------------------------
# autotuner: a failing measured candidate is skipped, not fatal
# ---------------------------------------------------------------------------

def test_tuner_skips_failing_measured_candidate():
    from repro import autotune as at

    m = unstructured(192, 7, seed=35)
    before = counters.snapshot()
    with chaos(kernel_failure=("tune:ell",)) as cfg:
        t = at.autotune(m, mode="measure", candidates=("csr", "ell", "hyb"))
    assert cfg.injected["kernel:tune:ell"] == 1
    assert "ell" not in (t.measured_s or {})
    assert t.format in ("csr", "hyb")
    after = counters.snapshot()
    assert after.get("tune.candidate_failed", 0) == \
        before.get("tune.candidate_failed", 0) + 1
    # the ranking decided under chaos must not have been cached
    t2 = at.autotune(m, mode="measure", candidates=("csr", "ell", "hyb"))
    assert "ell" in t2.measured_s


# ---------------------------------------------------------------------------
# solver guardrails (satellites 1 + 2 + stagnation)
# ---------------------------------------------------------------------------

class TestSolverGuardrails:
    def test_bicgstab_breakdown_detected_not_masked(self):
        """Regression (satellite 1): on A = [[0,1],[-1,0]], b = [1,0] the
        shadow-residual dot r̂·v is exactly zero at the first step.  The old
        code clamped the denominator to 1e-30 and kept iterating on a dead
        recurrence (alpha ~ 1e30: garbage iterates); the rewrite must stop
        with status "breakdown" and a finite iterate."""
        a = jnp.asarray([[0.0, 1.0], [-1.0, 0.0]], jnp.float32)
        b = jnp.asarray([1.0, 0.0], jnp.float32)
        r = bicgstab(lambda v: a @ v, b, tol=1e-8, max_iters=50)
        assert r.status == "breakdown"
        assert not bool(r.converged)
        assert np.isfinite(np.asarray(r.x)).all()
        assert np.isfinite(float(r.residual))

    def test_cg_breakdown_on_indefinite_operator(self):
        a = jnp.asarray(np.diag([1.0, -1.0]), jnp.float32)
        b = jnp.asarray([1.0, 1.0], jnp.float32)
        r = cg(lambda v: a @ v, b, tol=1e-8, max_iters=50)
        assert r.status == "breakdown"
        assert not bool(r.converged)
        assert np.isfinite(np.asarray(r.x)).all()

    def test_nan_matvec_classified_diverged(self):
        b = jnp.ones((8,), jnp.float32)
        bad = lambda v: jnp.full_like(v, jnp.nan)      # noqa: E731
        assert cg(bad, b, max_iters=5).status == "diverged"
        assert bicgstab(bad, b, max_iters=5).status == "diverged"
        # the rolled-back iterate stays finite either way
        assert np.isfinite(np.asarray(cg(bad, b, max_iters=5).x)).all()

    def test_stagnation_detected_at_unreachable_tol(self, rng):
        m = poisson3d(8)
        b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
        r = cg(_dense_mv(m), b, PRECONDITIONERS["jacobi"](m), tol=1e-30,
               max_iters=2000, stag_window=25, stag_rtol=0.05)
        assert r.status == "stagnated"
        assert int(r.iters) < 2000
        # the kept iterate is still the (machine-precision) solution
        ax = m.spmv(np.asarray(r.x, np.float64))
        assert np.linalg.norm(ax - np.asarray(b)) / \
            np.linalg.norm(np.asarray(b)) < 1e-4

    def test_healthy_trajectories_unchanged(self, rng):
        """Guardrails are branch-free selects: a converging solve must take
        exactly the iterates it always took."""
        m = poisson3d(8)
        b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
        r = cg(_dense_mv(m), b, PRECONDITIONERS["jacobi"](m), tol=1e-5,
               max_iters=1000)
        assert r.status == "converged" and bool(r.converged)
        m2 = unstructured(512, 10, seed=9)
        b2 = jnp.asarray(rng.standard_normal(m2.n), jnp.float32)
        r2 = bicgstab(_dense_mv(m2), b2, PRECONDITIONERS["jacobi"](m2),
                      tol=1e-5, max_iters=1000)
        assert r2.status == "converged" and bool(r2.converged)

    def test_status_property_backfills_legacy_results(self):
        r = SolveResult(x=jnp.zeros(2), iters=jnp.int32(3),
                        residual=jnp.float32(0.5),
                        converged=jnp.asarray(False))
        assert r.status == "maxiter"          # status_code defaults to None


class TestSolveFailureReporting:
    def test_maxiter_warns_structured(self, rng):
        """Satellite 2: a solve that returns non-converged must say so."""
        m = poisson3d(8)
        p = plan(m, execution=ExecutionConfig(format="ehyb",
                                              workload="solver"))
        op = p.bind(m)
        b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
        with pytest.warns(SolveFailureWarning, match="maxiter"):
            r = op.solve(b, tol=1e-10, max_iters=1)
        assert r.status == "maxiter" and not bool(r.converged)

    def test_raise_on_failure_carries_result(self, rng):
        m = poisson3d(8)
        p = plan(m, execution=ExecutionConfig(format="ehyb",
                                              workload="solver"))
        op = p.bind(m)
        b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
        with pytest.raises(SolveFailure) as ei:
            op.solve(b, tol=1e-10, max_iters=1, raise_on_failure=True)
        assert ei.value.result is not None
        assert ei.value.result.status == "maxiter"

    def test_nan_chaos_escalates_to_reference(self, rng):
        """Tentpole acceptance: silent kernel corruption (all-NaN applies)
        must be survived by the policy ladder — the reference re-run
        bypasses the corrupted kernel path and converges."""
        m = poisson3d(8)
        p = plan(m, execution=ExecutionConfig(format="ehyb",
                                              workload="solver"))
        op = p.bind(m)
        b = rng.standard_normal(m.n).astype(np.float32)
        before = counters.snapshot()
        with chaos(nan_apply=True) as cfg:
            r = op.solve(jnp.asarray(b), tol=1e-5, policy=SolvePolicy())
        assert cfg.injected["nan"] >= 1
        assert r.status == "converged"
        ax = m.spmv(np.asarray(r.x, np.float64))
        assert np.linalg.norm(ax - b) / np.linalg.norm(b) < 1e-4
        after = counters.snapshot()
        assert after.get("solver.escalate_reference", 0) > \
            before.get("solver.escalate_reference", 0)
        assert after.get("solver.recovered", 0) > \
            before.get("solver.recovered", 0)

    def test_policy_stagnation_status_without_escalation(self, rng):
        m = poisson3d(8)
        p = plan(m, execution=ExecutionConfig(format="ehyb",
                                              workload="solver"))
        op = p.bind(m)
        b = jnp.asarray(rng.standard_normal(m.n), jnp.float32)
        pol = SolvePolicy(max_restarts=0, escalate_method=False,
                          escalate_reference=False, stagnation_window=25,
                          stagnation_rtol=0.05)
        with pytest.warns(SolveFailureWarning, match="stagnated"):
            r = op.solve(b, tol=1e-30, max_iters=2000, policy=pol)
        assert r.status == "stagnated"


# ---------------------------------------------------------------------------
# bind-time validation (satellite 3)
# ---------------------------------------------------------------------------

class TestBindValidation:
    def _plan(self):
        m = unstructured(64, 5, seed=36)
        return m, plan(m, execution=ExecutionConfig(format="csr"))

    def test_nan_values_rejected(self):
        m, p = self._plan()
        vals = np.asarray(m.data, np.float64).copy()
        vals[1] = np.nan
        with pytest.raises(ValueError, match="non-finite"):
            p.bind(vals)

    def test_inf_values_rejected(self):
        m, p = self._plan()
        vals = np.asarray(m.data, np.float64).copy()
        vals[-1] = np.inf
        with pytest.raises(ValueError, match="non-finite"):
            p.bind(vals)

    def test_out_of_range_index_rejected(self):
        indptr = np.asarray([0, 1, 2], np.int64)
        indices = np.asarray([0, 7], np.int64)     # 7 >= n=2
        bad = SparseCSR(2, indptr, indices, np.asarray([1.0, 1.0]))
        p = plan(bad, execution=ExecutionConfig(format="csr"))
        with pytest.raises(ValueError, match="column indices outside"):
            p.bind(bad)

    def test_validate_false_opts_out(self):
        m, p = self._plan()
        vals = np.asarray(m.data, np.float64).copy()
        vals[1] = np.nan
        op = p.bind(vals, validate=False)
        assert op is not None                      # caller's poison, kept


# ---------------------------------------------------------------------------
# serving: admission control, deadlines, overload, chaos recovery
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_setup():
    from repro.configs import get_config
    from repro.models import init_model

    cfg = get_config("llama3_2_1b", smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _engine(serve_setup, **kw):
    from repro.serve import ServeEngine

    params, cfg = serve_setup
    kw.setdefault("batch", 1)
    kw.setdefault("max_len", 48)
    kw.setdefault("max_prompt", 8)
    return ServeEngine(params, cfg, **kw)


class TestServeAdmissionControl:
    def test_queue_flood_rejects_excess_and_finishes_admitted(
            self, serve_setup):
        """Tentpole acceptance: under a flood, excess requests are rejected
        with a reason and every admitted request finishes with its exact
        token count."""
        eng = _engine(serve_setup, max_queue=2)
        reqs = flood(eng, 6, max_new_tokens=3)
        rejected = [r for r in reqs if r.reject_reason == "queue_full"]
        admitted = [r for r in reqs if r.reject_reason is None]
        assert len(rejected) == 4 and len(admitted) == 2
        assert all(r.done for r in rejected)
        assert eng.health()["stats"]["rejected_queue_full"] == 4
        done = eng.run_until_done()
        finished = [r for r in done if r.reject_reason is None]
        assert sorted(r.uid for r in finished) == \
            sorted(r.uid for r in admitted)
        assert all(len(r.generated) == 3 for r in finished)

    def test_deadline_expires_queued_and_admitted(self, serve_setup):
        from repro.serve import Request

        t = [0.0]
        eng = _engine(serve_setup, clock=lambda: t[0],
                      policy=EnginePolicy(default_ttl_s=10.0))
        for i in range(3):
            eng.submit(Request(uid=i, prompt=np.arange(1, 5, dtype=np.int32),
                               max_new_tokens=6))
        done = eng.step()               # admits uid 0 into the single slot
        assert not done
        t[0] = 11.0                     # past every deadline
        done = eng.step()
        expired = {r.uid: r for r in done if r.reject_reason == "deadline"}
        assert sorted(expired) == [0, 1, 2]
        assert expired[0].generated     # admitted one keeps partial tokens
        stats = eng.health()["stats"]
        assert stats["expired_active"] == 1 and stats["expired_queued"] == 2

    def test_per_request_ttl_overrides_policy(self, serve_setup):
        from repro.serve import Request

        t = [0.0]
        eng = _engine(serve_setup, clock=lambda: t[0],
                      policy=EnginePolicy(default_ttl_s=1.0))
        eng.submit(Request(uid=0, prompt=np.arange(1, 5, dtype=np.int32),
                           max_new_tokens=2, ttl_s=100.0))
        t[0] = 5.0                      # past policy ttl, inside request ttl
        done = eng.run_until_done()
        assert len(done) == 1 and done[0].reject_reason is None
        assert len(done[0].generated) == 2

    def test_transient_apply_failure_retries_through(self, serve_setup):
        from repro.serve import Request

        eng = _engine(serve_setup)
        eng.submit(Request(uid=0, prompt=np.arange(1, 5, dtype=np.int32),
                           max_new_tokens=3))
        with chaos(serve_apply_failures=2) as cfg:
            done = eng.run_until_done()
        assert cfg.injected["serve:transient"] == 2
        assert len(done) == 1 and len(done[0].generated) == 3
        assert eng.stats["retries"] >= 2
        assert not eng.degraded         # transient: no degradation needed

    def test_sparse_head_failure_degrades_to_dense(self, serve_setup):
        """Tentpole acceptance: a persistently failing sparse head must not
        drop admitted requests — the engine degrades to the dense path and
        produces exactly what a dense engine would."""
        from repro.serve import Request

        prompt = np.arange(1, 7, dtype=np.int32)
        ref = _engine(serve_setup)
        ref.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
        want = ref.run_until_done()[0].generated

        eng = _engine(serve_setup, sparse_head_density=1.0)
        eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
        before = counters.snapshot()
        with chaos(fail_sparse_apply=True) as cfg:
            done = eng.run_until_done()
        assert cfg.injected["serve:sparse"] >= 1
        assert eng.degraded and eng.health()["degraded"]
        assert len(done) == 1 and done[0].generated == want
        after = counters.snapshot()
        assert after.get("serve.degraded", 0) == \
            before.get("serve.degraded", 0) + 1
        # the sparse layer survives: restore swaps it back in
        eng.restore_sparse_head()
        assert not eng.degraded
        eng.submit(Request(uid=1, prompt=prompt, max_new_tokens=2))
        done2 = eng.run_until_done()
        assert len(done2) == 1 and len(done2[0].generated) == 2

    def test_health_snapshot_shape(self, serve_setup):
        eng = _engine(serve_setup, max_queue=4)
        h = eng.health()
        assert h["queue_depth"] == 0 and h["active"] == 0
        assert h["max_queue"] == 4 and h["degraded"] is False
        assert isinstance(h["stats"], dict)
