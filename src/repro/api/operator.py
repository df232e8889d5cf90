"""Operator API v2: the value-bound, differentiable :class:`LinearOperator`.

A ``LinearOperator`` is what :meth:`repro.api.Plan.bind` returns: the plan's
chosen device format filled with one set of entry values.  It is

* **a pytree** — leaves are the device container's tables, aux is the plan
  (identity-hashed), so operators pass through ``jit``/``vmap``/``grad``
  boundaries and two binds of the same plan share one jit cache (rebinding
  new values triggers zero recompilation — pinned by tests/test_api.py);
* **one contract, local or sharded** — a plan built with ``mesh=`` binds an
  operator whose apply is the halo-exchange ``shard_map`` program, behind
  the same methods (``ShardedOperator`` is an engine behind this class, not
  a parallel API);
* **differentiable** — the original-space apply carries a ``custom_vjp``:
  the cotangent w.r.t. ``x`` is ``Aᵀ ḡ`` executed through a *transpose
  plan* derived from the same pattern (cache-shared, so symmetric FEM
  patterns reuse this very plan), and the cotangent w.r.t. the bound
  values is gathered per-nnz (``v̄ₖ = ḡ[rowₖ] · x[colₖ]``) and scattered
  into the value tables through the plan's probed value maps.  Only tables
  the apply actually reads receive cotangent — duplicate value copies kept
  for other execution paths stay at zero, so value gradients never double
  count.  Sharded applies compute ``Aᵀ ḡ`` by the direct per-nnz
  scatter-add (a transpose halo plan is future work).

Spaces: ``op @ x`` works in :attr:`Space.ORIGINAL`; hot loops hoist the
permutation with ``x̃ = op.to_space(x)`` / ``op.apply(x̃, space=
Space.PERMUTED)`` / ``op.from_space(ỹ)`` — the explicit form of the old
``to_permuted``/``from_permuted`` method pairs (kept as aliases).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Optional

import numpy as np

from ..core.counters import span
from ..core.matrices import SparseCSR
from .config import Space
from .plan import Plan


def _as_space(space) -> Space:
    if isinstance(space, Space):
        return space
    if space in ("original", "permuted"):
        return Space(space)
    raise ValueError(f"unknown space {space!r}; use repro.api.Space")


def _zeros_cotangent(leaf):
    import jax
    import jax.numpy as jnp

    if jnp.issubdtype(leaf.dtype, jnp.inexact):
        return jnp.zeros_like(leaf)
    return np.zeros(leaf.shape, jax.dtypes.float0)


def _make_diff_apply(plan: Plan):
    """The custom-VJP original-space apply for ``plan`` (built once per
    plan; jitted, so per-call dispatch is a cache lookup)."""
    import jax
    import jax.numpy as jnp

    raw = plan._raw_apply()
    # host numpy index arrays: kept OUT of jnp-land so the closure never
    # caches a tracer from whichever trace first builds this apply
    rows, cols = plan.coo()

    @jax.custom_vjp
    def apply(obj, x):
        return raw(obj, x)

    def fwd(obj, x):
        return raw(obj, x), (obj, x)

    def bwd(res, g):
        obj, x = res
        plan._ensure_value_maps()
        x2 = x[:, None] if x.ndim == 1 else x
        g2 = g[:, None] if g.ndim == 1 else g
        acc = jnp.promote_types(jnp.result_type(x2.dtype, g2.dtype),
                                jnp.float32)
        # cotangent w.r.t. the bound values, gathered per nnz
        vbar = jnp.einsum("kr,kr->k", g2[rows].astype(acc),
                          x2[cols].astype(acc))
        leaves, treedef = jax.tree_util.tree_flatten(obj)
        obj_bar = []
        for leaf, vm, act in zip(leaves, plan._maps, plan._active):
            if vm is None or not act:
                obj_bar.append(_zeros_cotangent(leaf))
            else:
                flat = jnp.zeros((vm["size"],), leaf.dtype)
                flat = flat.at[vm["dst"]].set(
                    vbar[vm["src"]].astype(leaf.dtype))
                obj_bar.append(flat.reshape(vm["shape"]))
        obj_bar = jax.tree_util.tree_unflatten(treedef, obj_bar)
        # cotangent w.r.t. x: Aᵀ ḡ
        vals = plan.values_of(obj)
        if plan.is_sharded:
            contrib = vals[:, None].astype(acc) * g2[rows].astype(acc)
            xbar2 = jnp.zeros((plan.n, g2.shape[1]), acc).at[cols].add(
                contrib)
            xbar = xbar2[:, 0] if x.ndim == 1 else xbar2
        else:
            # bind the transpose at the promoted accumulation dtype — like
            # the sharded branch above.  Binding at vals.dtype would
            # silently round an fp64 cotangent down to the stored values'
            # (typically fp32) precision before the Aᵀḡ apply.
            tplan = plan.transpose
            t_vals = vals[plan.transpose_order()]
            t_obj = tplan._bind_traced(t_vals.astype(acc), acc).obj
            xbar = tplan._raw_apply()(t_obj, g.astype(acc))
        return obj_bar, xbar.astype(x.dtype)

    apply.defvjp(fwd, bwd)
    return jax.jit(apply)


# ---------------------------------------------------------------------------
# the operator
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class LinearOperator:
    """A sparse matrix bound to its planned device format — see module
    docstring.  Construct with :meth:`repro.api.Plan.bind`."""

    plan: Plan
    obj: Any

    # best-effort host-side attrs (not pytree state; lost across flatten)
    _dtype: Any = dataclasses.field(default=None, repr=False)
    _csr: Optional[SparseCSR] = dataclasses.field(default=None, repr=False)
    _values: Optional[np.ndarray] = dataclasses.field(default=None,
                                                      repr=False)
    _fast: Any = dataclasses.field(default=None, repr=False)

    # ---- pytree ------------------------------------------------------------

    def tree_flatten(self):
        return (self.obj,), (self.plan,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(plan=aux[0], obj=leaves[0])

    # ---- identity ----------------------------------------------------------

    @property
    def n(self) -> int:
        return self.plan.n

    @property
    def nnz(self) -> int:
        return self.plan.nnz

    @property
    def shape(self) -> tuple:
        return (self.plan.n, self.plan.n)

    @property
    def format(self) -> str:
        return self.plan.format

    @property
    def tuning(self):
        return self.plan.tuning

    @property
    def dtype(self):
        import jax.numpy as jnp

        return self._dtype or jnp.float32

    @property
    def values(self) -> np.ndarray:
        """The bound per-nnz values in CSR order (host array)."""
        if self._values is not None:
            return self._values
        return np.asarray(self.plan.values_of(self.obj))

    @property
    def csr(self) -> SparseCSR:
        """Host CSR view of the bound matrix (pattern + current values)."""
        if self._csr is None:
            p = self.plan.pattern
            self._csr = SparseCSR(self.plan.n, p.indptr, p.indices,
                                  np.asarray(self.values, np.float64))
        return self._csr

    # ---- apply -------------------------------------------------------------

    def _promote(self, x):
        import jax.numpy as jnp

        x = jnp.asarray(x)
        if x.dtype.kind not in "fc":
            x = x.astype(self.dtype)
        return x

    def _diff_apply(self):
        fn = self.plan._diff_cache.get("apply")
        if fn is None:
            fn = self.plan._diff_cache["apply"] = _make_diff_apply(self.plan)
        return fn

    def __matmul__(self, x):
        # dispatch is hot (benchmarks/api_overhead.py holds it to <5% over
        # the raw engine apply): the engine closure is cached on the
        # instance, promotion is a duck-typed dtype check, and the
        # custom-vjp wrapper only enters when a transform is watching
        if _trace_clean():
            f = self._fast
            if f is None:
                f = self._fast = self.plan._raw_apply()
            dt = getattr(x, "dtype", None)
            if dt is not None and dt.kind in "fc":
                return f(self.obj, x)
            return f(self.obj, self._promote(x))
        dt = getattr(x, "dtype", None)
        if dt is None or dt.kind not in "fc":
            x = self._promote(x)
        return self._diff_apply()(self.obj, x)

    def __call__(self, x):
        return self @ x

    def apply(self, x, space: Space = Space.ORIGINAL):
        """``A @ x`` in the given space.  ``Space.ORIGINAL`` takes/returns
        length-``n`` vectors (or ``(n, R)`` batches) and is the
        differentiable path; ``Space.PERMUTED`` takes/returns
        ``(n_pad[, R])`` vectors in the execution space (the hot-loop form —
        no per-call permutation gathers)."""
        space = _as_space(space)
        if space is Space.ORIGINAL:
            return self @ x
        if not self.supports_permuted:
            raise ValueError(
                f"format {self.format!r} has no permuted execution space")
        return self.plan._raw_apply_permuted()(self.obj, self._promote(x))

    @property
    def matvec(self):
        """Bare ``x -> y`` closure, original space (Krylov-solver food)."""
        return self.__call__

    def _permuted_call(self, x_new):
        return self.plan._raw_apply_permuted()(self.obj, self._promote(x_new))

    @property
    def matvec_permuted(self):
        if not self.supports_permuted:
            raise ValueError(
                f"format {self.format!r} has no permuted execution space")
        return self._permuted_call

    # raw (obj, x) closures — the engine surface SparseLinear/serving route
    # device containers through as traced arguments
    @property
    def raw_apply(self):
        return self.plan._raw_apply()

    @property
    def raw_apply_permuted(self):
        return self.plan._raw_apply_permuted()

    # ---- spaces ------------------------------------------------------------

    @property
    def supports_permuted(self) -> bool:
        return self.plan._raw_apply_permuted() is not None

    @property
    def n_pad(self) -> int:
        return self.obj.n_pad if self.supports_permuted else self.n

    def to_space(self, x, space: Space = Space.PERMUTED):
        """Carry original-space vector(s) into ``space`` (once per loop)."""
        space = _as_space(space)
        if space is Space.ORIGINAL:
            return self._promote(x)
        if not self.supports_permuted:
            raise ValueError(
                f"format {self.format!r} has no permuted execution space")
        from ..core.spmv import _to_permuted

        xn, squeeze = _to_permuted(self.obj, self._promote(x))
        return xn[:, 0] if squeeze else xn

    def from_space(self, y, space: Space = Space.PERMUTED):
        """Carry vector(s) in ``space`` back to the original space."""
        space = _as_space(space)
        if space is Space.ORIGINAL:
            import jax.numpy as jnp

            return jnp.asarray(y)
        if not self.supports_permuted:
            raise ValueError(
                f"format {self.format!r} has no permuted execution space")
        from ..core.spmv import _as_2d, _from_permuted

        import jax.numpy as jnp

        y2, squeeze = _as_2d(jnp.asarray(y))
        return _from_permuted(self.obj, y2, squeeze)

    # legacy aliases (the old method-pair names)
    def to_permuted(self, x):
        return self.to_space(x, Space.PERMUTED)

    def from_permuted(self, y):
        return self.from_space(y, Space.PERMUTED)

    # ---- lifecycle ---------------------------------------------------------

    def update_values(self, values) -> "LinearOperator":
        """Same pattern, new values: one value refill, zero re-partitioning,
        zero recompilation (delegates to ``plan.bind``).

        Takes exactly one argument on purpose: the refill reuses the bound
        plan's dtype/format/mesh, so a keyword like ``dtype=`` here would be
        dead — and silently swallowing unknown keywords (as an older
        ``**_ignored`` signature did) turned typos into no-ops."""
        return self.plan.bind(values, dtype=self._dtype)

    def transpose(self) -> "LinearOperator":
        """``Aᵀ`` bound through the transpose plan (pattern-cache shared)."""
        t = self.plan.transpose_order()
        return self.plan.transpose.bind(self.values[t], dtype=self._dtype)

    @property
    def T(self) -> "LinearOperator":
        return self.transpose()

    @property
    def halo_plan(self):
        """The sharded plan's halo-exchange schedule
        (:class:`repro.dist.HaloPlan`; None for local plans)."""
        if not self.plan.is_sharded:
            return None
        import jax.numpy as jnp

        return self.plan._template_for(self._dtype or jnp.float32).plan

    def solve(self, b, *, method: str = "cg", precond: str = "jacobi",
              x0=None, tol: float = 1e-6, max_iters: int = 500,
              space="auto", fused_update="auto", policy=None,
              raise_on_failure: bool = False, warn: bool = True):
        """Solve ``A x = b`` with this operator driving the Krylov loop —
        distributed automatically when the plan is sharded.  ``x0`` warm
        starts the iteration (permuted once into the execution space
        alongside ``b``).

        A non-converged final status always either warns
        (:class:`~repro.reliability.SolveFailureWarning`, default) or
        raises (:class:`~repro.reliability.SolveFailure` with the result
        attached, ``raise_on_failure=True``) — never a silent
        ``converged=False``.  Passing a
        :class:`~repro.reliability.SolvePolicy` arms the in-loop
        stagnation/divergence sentinels and the host escalation ladder
        (restart → method escalation → reference apply); see
        ``repro.reliability`` DESIGN."""
        return solve_operator(self, b, method=method, precond=precond,
                              x0=x0, tol=tol, max_iters=max_iters,
                              space=space, fused_update=fused_update,
                              policy=policy,
                              raise_on_failure=raise_on_failure, warn=warn)


import jax  # noqa: E402  (registration needs jax; kept after the class)

jax.tree_util.register_pytree_node_class(LinearOperator)

from ..compat import trace_state_clean as _trace_clean  # noqa: E402


# ---------------------------------------------------------------------------
# solving (one engine for local and sharded operators)
# ---------------------------------------------------------------------------

def _solve_sharded_engine(sop, b, *, csr, method, precond, x0, tol,
                          max_iters, obj=None):
    """Distributed solve on a ShardedOperator engine (whole Krylov
    ``while_loop`` inside one shard_map; see core.solver DESIGN)."""
    import jax.numpy as jnp

    from ..core.solver import SolveResult, _cached_precond

    from ..autotune.cost import matrix_key

    inv = None
    if precond != "none":
        if csr is None:
            raise ValueError(
                "a preconditioned distributed solve needs the operator's "
                "host matrix; bind the plan from a SparseCSR or pass "
                "precond='none'")
        key = matrix_key(csr)
        _, inv = _cached_precond(csr, precond, key, perm=sop.perm_host,
                                 n_pad=sop.n_pad)
    b = jnp.asarray(b)
    acc = jnp.promote_types(b.dtype, jnp.float32)
    inv_arr = (jnp.ones((sop.n_pad,), acc) if inv is None
               else jnp.asarray(inv, acc))
    if b.ndim > 1:
        inv_arr = inv_arr[:, None]
    b_new = sop.to_permuted(b)
    x0_new = (jnp.zeros_like(b_new) if x0 is None
              else sop.to_permuted(jnp.asarray(x0, b.dtype)))
    run = sop.solver_runner(method)
    r = run(sop.obj if obj is None else obj, b_new, x0_new, inv_arr, tol,
            max_iters=max_iters)
    return SolveResult(x=sop.from_permuted(r.x), iters=r.iters,
                       residual=r.residual, converged=r.converged,
                       status_code=r.status_code)


def _reference_solve(op, b, *, method, precond, x0, tol, max_iters,
                     kw_guard):
    """Escalation rung 3: re-run the Krylov loop on a pure lax/gather CSR
    matvec built straight from the operator's host matrix — no planned
    kernels, no permuted space — so it recovers even from kernel-level
    output corruption the capability probe cannot see."""
    import jax.numpy as jnp

    from ..autotune.cost import matrix_key
    from ..core import solver as S

    a = op.csr
    rows = np.repeat(np.arange(a.n), a.row_lengths())
    cols = np.asarray(a.indices)
    b = jnp.asarray(b)
    vals = jnp.asarray(a.data, b.dtype)
    acc = jnp.promote_types(b.dtype, jnp.float32)

    def mv(x):
        x2 = x[:, None] if x.ndim == 1 else x
        contrib = vals[:, None].astype(acc) * x2[cols].astype(acc)
        y = jnp.zeros((a.n, x2.shape[1]), acc).at[rows].add(contrib)
        y = y.astype(x2.dtype)
        return y[:, 0] if x.ndim == 1 else y

    pre, _ = S._cached_precond(a, precond, matrix_key(a))
    return S.SOLVERS[method](mv, b, pre, tol=tol, max_iters=max_iters,
                             x0=x0, **kw_guard)


def _better(r_old, r_new):
    """The more useful of two solve attempts: converged wins; otherwise the
    smaller finite residual (NaN never beats a finite iterate)."""
    import math

    if bool(r_new.converged):
        return r_new
    if bool(r_old.converged):
        return r_old
    res_new = float(r_new.residual)
    res_old = float(r_old.residual)
    if math.isfinite(res_new) and not math.isfinite(res_old):
        return r_new
    if math.isfinite(res_old) and not math.isfinite(res_new):
        return r_old
    return r_new if res_new <= res_old else r_old


_SOLVE_IDS = itertools.count(1)


def _solve_span(fn):
    """``fn`` run inside a ``repro.solve`` span that carries a running
    solve id into the trace; the solve's stage spans nest inside it."""
    @functools.wraps(fn)
    def spanned(*args, **kwargs):
        with span("repro.solve", id=next(_SOLVE_IDS)):
            return fn(*args, **kwargs)
    return spanned


@_solve_span
def solve_operator(op, b, *, method: str = "cg", precond: str = "jacobi",
                   x0=None, tol: float = 1e-6, max_iters: int = 500,
                   space="auto", fused_update="auto", policy=None,
                   raise_on_failure: bool = False, warn: bool = True):
    """Solve ``A x = b`` on a bound operator (the engine behind both
    :meth:`LinearOperator.solve` and the deprecated ``core.solver.solve``).

    Accepts a :class:`LinearOperator` (local or sharded plan) or a bare
    :class:`repro.dist.ShardedOperator` engine.  ``x0`` (optional) warm
    starts the Krylov iteration; like ``b`` it is permuted once into the
    execution space, never per iteration.

    Failure handling (host-side, skipped when the result is traced):

    * a final non-converged status warns once
      (:class:`~repro.reliability.SolveFailureWarning`) or, with
      ``raise_on_failure=True``, raises
      :class:`~repro.reliability.SolveFailure` carrying the result;
    * a :class:`~repro.reliability.SolvePolicy` arms the solver's
      stagnation/divergence sentinels and the escalation ladder — warm
      restarts, cg→bicgstab, then the reference CSR apply (local
      operators only; sharded solves report but do not escalate).
    """
    import jax
    import jax.numpy as jnp

    from ..core import solver as S
    from ..dist.operator import ShardedOperator

    if method not in S.SOLVERS:
        raise ValueError(f"unknown method {method!r}; "
                         f"have {sorted(S.SOLVERS)}")
    if isinstance(op, ShardedOperator):
        r = _solve_sharded_engine(op, b, csr=op.csr, method=method,
                                  precond=precond, x0=x0, tol=tol,
                                  max_iters=max_iters)
        return _finalize_solve(r, (), raise_on_failure, warn)
    if op.plan.is_sharded:
        tpl = op.plan._template_for(op._dtype or jnp.float32)
        r = _solve_sharded_engine(tpl, b, csr=op.csr, method=method,
                                  precond=precond, x0=x0, tol=tol,
                                  max_iters=max_iters, obj=op.obj)
        return _finalize_solve(r, (), raise_on_failure, warn)
    if space in ("auto", None):
        use_perm = op.supports_permuted
    else:
        use_perm = _as_space(space) is Space.PERMUTED
    if use_perm and not op.supports_permuted:
        raise ValueError(
            f"format {op.format!r} has no permuted execution space")
    if fused_update is True and method != "cg":
        raise ValueError(
            f"fused_update is a CG-step kernel; method {method!r} has no "
            f"fused vector-update path")
    if fused_update == "auto":
        # TPU only: the fused kernel's cross-grid-step dots accumulation
        # relies on the sequential TPU grid (racy on parallel GPU grids)
        fused_update = jax.default_backend() == "tpu" and method == "cg"
    a = op.csr
    from ..autotune.cost import matrix_key

    with span("repro.solve.key"):
        key = matrix_key(a)
    with span("repro.solve.precond"):
        if use_perm:
            pre, inv = S._cached_precond(a, precond, key,
                                         perm=np.asarray(op.obj.perm),
                                         n_pad=op.n_pad)
        else:
            pre, inv = S._cached_precond(a, precond, key)
    with span("repro.solve.to_space"):
        b = jnp.asarray(b)
        b_run = op.to_space(b, Space.PERMUTED) if use_perm else b
    mv = op.matvec_permuted if use_perm else op.matvec
    kw_guard = {}
    if policy is not None:
        kw_guard = {"stag_window": policy.stagnation_window,
                    "stag_rtol": policy.stagnation_rtol,
                    "div_factor": policy.divergence_factor}

    def _run_local(method_, x0_orig):
        x0_run = None
        if x0_orig is not None:
            with span("repro.solve.to_space"):
                x0a = jnp.asarray(x0_orig, b.dtype)
                x0_run = (op.to_space(x0a, Space.PERMUTED) if use_perm
                          else x0a)
        kw = dict(kw_guard)
        if method_ == "cg":
            kw.update(fused_update=bool(fused_update),
                      precond_inv=None if inv is None
                      else jnp.asarray(inv, jnp.promote_types(b.dtype,
                                                              jnp.float32)))
        elif policy is not None and policy.breakdown_tol is not None:
            kw["breakdown_tol"] = policy.breakdown_tol
        with span("repro.solve.loop"):
            r = S.SOLVERS[method_](mv, b_run, pre, tol=tol,
                                   max_iters=max_iters, x0=x0_run, **kw)
        if use_perm:
            with span("repro.solve.from_space"):
                r = S.SolveResult(x=op.from_space(r.x, Space.PERMUTED),
                                  iters=r.iters, residual=r.residual,
                                  converged=r.converged,
                                  status_code=r.status_code)
        return r

    r = _run_local(method, x0)
    stages: list = []
    if (policy is not None and not isinstance(r.converged, jax.core.Tracer)
            and not bool(r.converged)):
        import warnings as _w

        from ..core.counters import bump as _bump
        from ..reliability.policy import ReliabilityWarning

        def _warm(res):
            return (res.x if bool(jnp.isfinite(res.x).all())
                    else x0)   # never warm start from a corrupted iterate

        cur = method
        restarts = 0
        while (not bool(r.converged) and r.status != "breakdown"
               and restarts < policy.max_restarts):
            restarts += 1
            _bump("solver.restart")
            stages.append(f"restart[{cur}]")
            r = _better(r, _run_local(cur, _warm(r)))
        if (not bool(r.converged) and policy.escalate_method
                and cur == "cg"):
            cur = "bicgstab"
            _bump("solver.escalate_method")
            stages.append("escalate:bicgstab")
            r = _better(r, _run_local(cur, _warm(r)))
        if not bool(r.converged) and policy.escalate_reference:
            _bump("solver.escalate_reference")
            stages.append("escalate:reference")
            kw_ref = dict(kw_guard)
            if policy.breakdown_tol is not None and cur == "bicgstab":
                kw_ref["breakdown_tol"] = policy.breakdown_tol
            r = _better(r, _reference_solve(
                op, b, method=cur, precond=precond, x0=_warm(r), tol=tol,
                max_iters=max_iters, kw_guard=kw_ref))
        if stages:
            _w.warn(
                f"solve escalated through {', '.join(stages)} "
                f"(final status {r.status!r})", ReliabilityWarning,
                stacklevel=2)
    return _finalize_solve(r, tuple(stages), raise_on_failure, warn)


def _finalize_solve(r, stages, raise_on_failure, warn):
    """Terminal accounting: a non-converged result is never silent.  The
    ``repro.solve.finalize`` span times ``bool(r.converged)``, which waits
    for the device."""
    import jax

    if isinstance(r.converged, jax.core.Tracer):
        return r           # traced solve: the caller sees the status array
    from ..core.counters import bump as _bump
    from ..reliability.policy import SolveFailure, SolveFailureWarning

    with span("repro.solve.finalize"):
        converged = bool(r.converged)
    if converged:
        if stages:
            _bump("solver.recovered")
        return r
    _bump("solver.failed")
    msg = (f"solve did not converge: status={r.status!r}, "
           f"residual={float(r.residual):.3e}, iters={int(r.iters)}")
    if stages:
        msg += f"; escalation tried: {', '.join(stages)}"
    if raise_on_failure:
        raise SolveFailure(msg, result=r)
    if warn:
        import warnings as _w

        _w.warn(msg, SolveFailureWarning, stacklevel=3)
    return r
