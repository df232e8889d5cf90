"""Operator API v2: the pattern-only :class:`Plan` and its visible cache.

The paper's economic argument (§3, §4.3) is that EHYB preprocessing is paid
once per sparsity pattern and amortized across many SpMVs.  This module
makes that lifecycle a first-class object instead of a convention smeared
across entry points:

    p  = plan(A)                  # pattern-only: partitioning, format
                                  # choice, halo schedule, permutations
    op = p.bind(A)                # values -> LinearOperator (device tables)
    y  = op @ x                   # apply (differentiable, jit/vmap-safe)
    op = op.update_values(A2)     # same pattern, new values: refill only

Everything value-independent lives on the ``Plan``; everything value-bound
lives on the :class:`~repro.api.operator.LinearOperator` it binds.  Plans
are memoized in ONE visible :class:`PlanCache` (``repro.api.PLAN_CACHE``),
which replaces the module-level ``_OP_CACHE``/``_OP_PATTERN_CACHE`` globals
that used to hide in ``core.spmv`` and the ``_HOST_EHYB`` pair in
``autotune.registry``.

Differentiability: a plan also records, lazily, the **value maps** of its
chosen format — for every device value table the static (dst, src) index
pair such that ``table.flat[dst] = values[src]`` reproduces the table from
the canonical per-nnz CSR value array.  The maps are probed from the
format's own refill hook (fill distinguishable values, read back where they
landed), so any registered format — including ones added later — inherits
traceable ``bind`` and the custom-VJP apply without format-specific
autodiff code.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import numpy as np

from ..core.cache import BoundedCache
from ..core.matrices import SparseCSR
from .config import ExecutionConfig


def _is_traced(x) -> bool:
    from ..compat import is_tracer

    return is_tracer(x)


def _run_untraced(fn):
    """Run host-side bookkeeping outside any ambient jax trace.

    Plan probing and template building execute concrete jnp computations
    (refills, device uploads, reference applies).  They may be reached
    lazily from inside a jit/grad trace — custom-vjp bwd, traced bind —
    where jax's ambient tracing would capture those throwaway computations
    as tracers (and pallas kernels refuse traced closure constants).  JAX
    trace contexts are thread-local, so a worker thread gives us a clean,
    trace-free evaluation context.
    """
    import threading

    if threading.current_thread().name.startswith("repro-plan"):
        return fn()          # already on the clean worker; nesting is fine
    global _UNTRACED_POOL
    if _UNTRACED_POOL is None:
        import concurrent.futures

        _UNTRACED_POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="repro-plan")
    return _UNTRACED_POOL.submit(fn).result()


_UNTRACED_POOL = None


def _partition_of(e):
    """Reconstruct the :class:`~repro.core.Partition` behind a host EHYB
    build (for persisting a cold plan's partitioning work).  ``perm`` /
    ``inv_perm`` are carried verbatim; ``part_vec`` falls out of the slot
    layout (vertices of partition p occupy slots [p*V, (p+1)*V))."""
    if e is None:
        return None
    from ..core.partition import Partition

    inv = np.asarray(e.inv_perm)
    return Partition(
        n=e.n, n_pad=e.n_pad, n_parts=e.n_parts, vec_size=e.vec_size,
        part_vec=(inv[:e.n] // e.vec_size).astype(np.int32),
        perm=np.asarray(e.perm, np.int64), inv_perm=inv.astype(np.int64),
        method=getattr(e, "partition_method", "bfs"), seconds=0.0)


# ---------------------------------------------------------------------------
# the plan cache (the one visible memo replacing the old module globals)
# ---------------------------------------------------------------------------

class PlanCache:
    """Bounded LRU of :class:`Plan` objects keyed by
    ``(pattern hash, ExecutionConfig token, mesh, axis)`` plus the host-side
    EHYB build memo the whole format family shares.

    The host memo is two-level, as before: an exact (value-inclusive) hit
    returns the build as-is; a *pattern* hit — same ``indptr``/``indices``,
    new values — refills the cached build through its recorded scatter plan
    instead of re-partitioning.
    """

    def __init__(self, maxsize: int = 32):
        self._plans = BoundedCache(maxsize=maxsize)
        self._host = BoundedCache(maxsize=maxsize)          # matrix key
        self._host_pattern = BoundedCache(maxsize=maxsize)  # pattern hash

    # ---- plans -------------------------------------------------------------

    def plan_for(self, pattern: SparseCSR, mesh=None, axis: str = "data",
                 execution: Optional[ExecutionConfig] = None) -> "Plan":
        from ..autotune.cost import pattern_hash

        execution = execution or ExecutionConfig()
        key = pattern_hash(pattern)
        ck = (key, execution.token(), None if mesh is None else (mesh, axis))
        p = self._plans.get(ck)
        if p is None:
            p = Plan._create(pattern, key, mesh, axis, execution, self)
            self._plans[ck] = p
        return p

    # ---- shared host EHYB build (one partitioning pass per pattern) --------

    def host_ehyb(self, m: SparseCSR, method: str = "bfs", part=None):
        """Host EHYB build memo, keyed by (matrix, partition strategy).

        ``part`` (a prebuilt :class:`~repro.core.Partition`, e.g. the
        ``autotune_partition`` winner) seeds a cold build so the strategy's
        partitioning pass is never repeated; pattern-level hits under the
        same strategy refill the cached build's value tables instead of
        re-partitioning."""
        from ..autotune.cost import matrix_key, pattern_hash
        from ..core.ehyb import build_ehyb

        pkey = pattern_hash(m)
        key = (matrix_key(m, pkey), method)
        e = self._host.get(key)
        if e is None:
            prev = self._host_pattern.get((pkey, method))
            if prev is not None and prev.fill_plan is not None:
                e = prev.refill(m.data)
            elif part is not None:
                e = build_ehyb(m, part=part)
            else:
                e = build_ehyb(m, method=method)
            self._host[key] = e
            self._host_pattern[(pkey, method)] = e
        return e

    # ---- persistent tune/plan store (repro.tuning.store) -------------------

    @staticmethod
    def store():
        """The active on-disk tune store, or None (in-memory only)."""
        from ..tuning.store import get_store

        return get_store()

    def load(self, key: str, context: str, *, dtype=None, k: int = 1,
             n_dev: int = 1):
        """Stored ``(TuneEntry, Partition)`` for a pattern-hash/config, or
        ``(None, None)`` — corruption is quarantined, stale versions are
        evicted, and the store's hit/miss counters record the outcome."""
        st = self.store()
        if st is None:
            return None, None
        import jax
        import jax.numpy as jnp

        res = st.load(key, jax.default_backend(),
                      jnp.dtype(dtype or jnp.float32).name, context,
                      k, n_dev)
        return (None, None) if res is None else res

    def save(self, plan: "Plan") -> bool:
        """Persist a plan's tuned decisions (format, partition strategy +
        arrays, tuned kernel parameters) into the active store.  No-op
        without a store; refused while fault injection is active."""
        st = self.store()
        if st is None:
            return False
        import jax
        import jax.numpy as jnp

        from ..tuning.store import TuneEntry

        part = (plan.partition_tuning.partition
                if plan.partition_tuning is not None else None)
        if part is None:
            part = _partition_of(plan._shared.get("ehyb"))
        n_dev = plan.mesh.shape[plan.axis] if plan.mesh is not None else 1
        entry = TuneEntry(
            pattern=plan.key, backend=jax.default_backend(),
            dtype=jnp.dtype(plan.execution.dtype or jnp.float32).name,
            context=plan.context, k=plan.execution.k, n_dev=n_dev,
            format=plan.format, partition_method=plan.partition_strategy,
            tuned=plan.tuned.to_dict() if plan.tuned is not None else {},
            meta={"n": plan.n, "nnz": plan.nnz,
                  "mode": plan.execution.mode})
        return st.save(entry, part)

    def evict(self, pattern: Optional[str] = None) -> int:
        """Evict persisted entries (all, or one pattern hash) from the
        active store; returns the number of entries removed."""
        st = self.store()
        return 0 if st is None else st.evict(pattern)

    # ---- bookkeeping -------------------------------------------------------

    def __len__(self) -> int:
        return len(self._plans)

    def clear(self) -> None:
        self._plans.clear()
        self._host.clear()
        self._host_pattern.clear()

    def stats(self) -> dict:
        """In-memory plan/host-build counts plus the tune layer: the
        autotuner's decision memo and, when a persistent store is active,
        its disk hit/miss/stale/quarantine counters."""
        from ..autotune.tuner import tune_cache_info

        return {"plans": len(self._plans), "host_builds": len(self._host),
                "host_patterns": len(self._host_pattern),
                "tune": tune_cache_info()}


PLAN_CACHE = PlanCache()


def plan(pattern: SparseCSR, *, mesh=None, mesh_axis: str = "data",
         execution: Optional[ExecutionConfig] = None,
         cache: Optional[PlanCache] = None) -> "Plan":
    """Plan the operator lifecycle for a sparsity pattern.

    ``pattern`` is a :class:`SparseCSR`; only its ``indptr``/``indices``
    determine the plan (its values merely seed the autotuner's measured mode
    and the first ``bind``).  ``mesh`` plans a sharded operator over
    ``mesh[mesh_axis]`` (halo schedule included).  Plans are memoized in
    ``cache`` (default: the module-level :data:`PLAN_CACHE`).
    """
    if not isinstance(pattern, SparseCSR):
        raise TypeError(f"plan() takes a SparseCSR pattern, "
                        f"got {type(pattern).__name__}")
    return (cache or PLAN_CACHE).plan_for(pattern, mesh, mesh_axis, execution)


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class Plan:
    """Pattern-only execution plan: format choice, partitioning/permutation,
    halo schedule — everything cacheable per sparsity pattern.  Identity is
    the pytree-aux anchor for every operator bound from it, so two binds of
    the same plan always share one jit cache.
    """

    key: str                        # sparsity-pattern hash
    n: int
    nnz: int
    format: str                     # chosen format name
    context: str                    # autotuner context this plan ranked for
    execution: ExecutionConfig
    mesh: Any = None
    axis: str = "data"
    tuning: Any = None              # TuneResult | None
    partition_strategy: Optional[str] = None  # strategy behind the host EHYB
    partition_tuning: Any = None    # PartitionTuneResult | None
    tuned: Any = None               # resolved TunedParams (never None after
    #                                 _create: pin > store > sweep > defaults)
    pattern: SparseCSR = None       # pattern holder (values = plan seed)
    cache: Any = None               # owning PlanCache (host-build memo)
    # ---- lazy value-bound state -------------------------------------------
    _shared: dict = dataclasses.field(default_factory=dict)
    _templates: dict = dataclasses.field(default_factory=dict)
    _maps: Optional[List] = None          # per-leaf value maps (see probe)
    _active: Optional[List] = None        # per-leaf: leaf feeds the apply
    _recovery: Optional[List] = None      # minimal leaf cover of all nnz
    _treedef: Any = None
    _diff_cache: dict = dataclasses.field(default_factory=dict)
    _perm_cache: dict = dataclasses.field(default_factory=dict)
    _t_order: Optional[np.ndarray] = None
    _coo: Optional[Tuple[np.ndarray, np.ndarray]] = None
    _guards: dict = dataclasses.field(default_factory=dict)
    _indices_ok: Optional[bool] = None    # bind-time index check, memoized

    # ---- construction ------------------------------------------------------

    @classmethod
    def _create(cls, pattern: SparseCSR, key: str, mesh, axis: str,
                execution: ExecutionConfig, cache: PlanCache) -> "Plan":
        from .. import autotune as at

        shared: dict = {}
        n_dev = mesh.shape[axis] if mesh is not None else 1
        if mesh is not None and n_dev > 1:
            if execution.workload not in ("auto", "dist"):
                raise ValueError(
                    f"workload {execution.workload!r} conflicts with a "
                    f"{n_dev}-device mesh: sharded plans rank with the "
                    f"interconnect-aware 'dist' cost model")
            context = "dist"
        elif mesh is not None:
            # degenerate 1-device mesh: no interconnect to price — "auto"
            # ranks like a hot loop (matching the legacy build_sharded_spmv)
            context = (execution.workload
                       if execution.workload in ("spmv", "solver")
                       else "solver")
        elif execution.workload == "dist":
            raise ValueError("workload='dist' prices a multi-device mesh; "
                             "pass mesh= with more than one device")
        else:
            context = ("spmv" if execution.workload == "auto"
                       else execution.workload)
        tuning = None
        fmt = execution.format
        shardable = ()
        if mesh is not None:
            shardable = tuple(f for f in at.available_formats()
                              if at.get_format(f).shard is not None)
            if fmt != "auto" and at.get_format(fmt).shard is None:
                raise ValueError(
                    f"format {fmt!r} carries no partition structure to "
                    f"shard; pick one of {sorted(shardable)}")
        # ---- persistent tune store consult --------------------------------
        # A stored entry for this (pattern, backend, dtype, context, k,
        # n_dev) warm-starts the whole decision stack: format, partition
        # strategy + the Partition arrays themselves, and the tuned kernel
        # parameters — a fresh process reaches a bound operator with zero
        # re-partitioning and zero tuner measurements.  Explicit config pins
        # always win over the store; an entry whose format a pinned
        # candidate set (or mesh shardability) rules out is ignored.
        from ..tuning.params import resolve as _resolve_params

        entry, part_loaded = cache.load(key, context, dtype=execution.dtype,
                                        k=execution.k, n_dev=n_dev)
        if entry is not None:
            allowed = execution.candidates or at.available_formats()
            if fmt == "auto" and (entry.format not in allowed or (
                    mesh is not None and entry.format not in shardable)):
                entry, part_loaded = None, None
        # ---- partition strategy (joins the autotune decision) -------------
        # An unset partition_method autotunes the strategy whenever an
        # EHYB-family format may be selected: every registered strategy is
        # priced with the partition-level bytes-moved model in this plan's
        # context (dist pricing includes the scheduled halo words), and the
        # winner's Partition seeds the shared host build.  The choice rides
        # the plan-cache token via ExecutionConfig.token(), so plans pinned
        # to different strategies coexist and rebinds stay refill-only.
        method = execution.partition_method
        ptuning = None
        if (method is None and entry is not None
                and entry.partition_method is not None):
            method = entry.partition_method
        elif method is None:
            needs_part = (any(at.get_format(f).shard is not None
                              for f in (execution.candidates
                                        or at.available_formats()))
                          if fmt == "auto"
                          else at.get_format(fmt).shard is not None)
            if needs_part:
                import jax.numpy as jnp

                kw = {"n_dev": n_dev} if context == "dist" else {}
                ptuning = at.autotune_partition(
                    pattern, context=context,
                    val_bytes=jnp.dtype(execution.dtype
                                        or jnp.float32).itemsize, **kw)
                method = ptuning.strategy
        if method is not None:
            part_seed = (ptuning.partition if ptuning is not None
                         else part_loaded)
            shared["ehyb"] = cache.host_ehyb(pattern, method=method,
                                             part=part_seed)
        # ---- tuned kernel parameters + format ------------------------------
        tuned = execution.tuned
        if tuned is None and entry is not None:
            tuned = entry.tuned_params()
        if entry is not None and fmt == "auto":
            # full warm start: the stored decision replaces the autotune
            # pass entirely (its counters stay untouched — asserted by the
            # persistence tests)
            fmt = entry.format
            at.get_format(fmt)
        elif fmt == "auto":
            cand = execution.candidates
            if mesh is not None:
                cand = tuple(f for f in (cand or shardable) if f in shardable)
            kw = {"n_dev": n_dev} if context == "dist" else {}
            tuning = at.autotune(pattern, execution.dtype,
                                 mode=execution.mode, candidates=cand,
                                 shared=shared, context=context,
                                 k=execution.k, tuned=tuned, **kw)
            fmt = tuning.format
            if tuned is None and tuning.tuned is not None:
                from ..tuning.params import TunedParams

                tuned = TunedParams.from_dict(tuning.tuned)
        else:
            at.get_format(fmt)          # validate the name early
        tuned = _resolve_params(tuned)
        shared["tuned"] = tuned
        p = cls(key=key, n=pattern.n, nnz=pattern.nnz, format=fmt,
                context=context, execution=execution, mesh=mesh,
                axis=axis, tuning=tuning, partition_strategy=method,
                partition_tuning=ptuning, tuned=tuned, pattern=pattern,
                cache=cache, _shared=shared)
        if entry is None:
            cache.save(p)        # no-op without an active store
        return p

    # ---- binding -----------------------------------------------------------

    def _default_dtype(self):
        import jax.numpy as jnp

        return self.execution.dtype or jnp.float32

    def _as_csr(self, values) -> Tuple[SparseCSR, np.ndarray]:
        """Normalize concrete bind input to (csr, per-nnz data)."""
        if isinstance(values, SparseCSR):
            from ..autotune.cost import pattern_hash

            if values.n != self.n or values.nnz != self.nnz or \
                    pattern_hash(values) != self.key:
                raise ValueError(
                    "bind() needs values on this plan's sparsity pattern; "
                    "call repro.api.plan() for a new pattern")
            return values, values.data
        data = np.asarray(values, dtype=np.float64)
        if data.shape != (self.nnz,):
            raise ValueError(f"bind() takes a ({self.nnz},) per-nnz value "
                             f"array (CSR order) or a SparseCSR; "
                             f"got shape {data.shape}")
        return SparseCSR(self.n, self.pattern.indptr, self.pattern.indices,
                         data), data

    def _validate_bind(self, data: np.ndarray) -> None:
        """Bind-time input validation: non-finite values and out-of-range
        column indices both produce garbage *silently* downstream (NaN
        pollutes every iterate; a bad index gathers from the wrong vertex
        or out of bounds, which XLA clamps rather than reports).  Reject at
        the API boundary instead.  The index check is pattern-level and
        memoized; the value check is one vectorized ``isfinite`` pass."""
        if not np.isfinite(data).all():
            bad = int((~np.isfinite(np.asarray(data))).sum())
            raise ValueError(
                f"bind() got {bad} non-finite value(s); a NaN/Inf entry "
                f"silently corrupts every downstream apply/solve "
                f"(pass validate=False to bind anyway)")
        if self._indices_ok is None:
            idx = np.asarray(self.pattern.indices)
            self._indices_ok = bool(
                idx.size == 0 or (idx.min() >= 0 and idx.max() < self.n))
        if not self._indices_ok:
            raise ValueError(
                f"plan pattern carries column indices outside [0, {self.n})"
                f"; the gather they feed is undefined "
                f"(pass validate=False to bind anyway)")

    def bind(self, values, *, dtype=None,
             validate=True) -> "LinearOperator":
        """Bind entry values to the planned structure -> LinearOperator.

        ``values`` is a :class:`SparseCSR` on this plan's pattern or a
        ``(nnz,)`` per-nnz array in CSR order.  Concrete values take the
        host refill fast path (zero re-partitioning, zero recompilation);
        traced values (inside ``jit``/``grad``/``vmap``) are scattered into
        the value tables in-graph through the plan's value maps, which is
        what makes ``grad`` through ``bind`` work.

        ``validate=True`` (default) rejects non-finite values and
        out-of-range column indices at the boundary (concrete binds only —
        traced values cannot be host-inspected); ``validate=False`` opts
        out for callers that stage NaN payloads deliberately;
        ``validate="full"`` additionally runs the format's complete static
        verifier (``repro.analysis.verify``) on the bound operator —
        permutation bijectivity, staircase/padding discipline, fill-plan
        and halo conservation laws — and raises on any error finding.
        """
        from .operator import LinearOperator

        import jax.numpy as jnp

        dtype = dtype or self._default_dtype()
        if _is_traced(values) or (not isinstance(values, SparseCSR)
                                  and _is_traced(jnp.asarray(values))):
            return self._bind_traced(values, dtype)
        csr, data = self._as_csr(values)
        if validate:
            self._validate_bind(data)
        tpl = self._template_for(dtype, csr)
        op = LinearOperator(plan=self, obj=tpl.obj)
        op._dtype = jnp.dtype(dtype)
        op._csr = csr
        op._values = data
        if validate == "full":
            from ..analysis import errors, verify

            bad = errors(verify(op))
            if bad:
                detail = "; ".join(str(f) for f in bad[:4])
                raise ValueError(
                    f"bind(validate='full'): {len(bad)} invariant "
                    f"violation(s) in the bound {self.format!r} container: "
                    f"{detail}")
        return op

    def _template_for(self, dtype, csr: Optional[SparseCSR] = None):
        """The per-dtype engine operator (SpMVOperator / ShardedOperator),
        built on first bind and value-refilled on later binds."""
        import jax.numpy as jnp

        from ..autotune.cost import matrix_key

        dt_name = jnp.dtype(dtype).name
        seed = csr if csr is not None else self.pattern
        mk = matrix_key(seed, self.key)
        slot = self._templates.get(dt_name)
        if slot is None:
            tpl = self._build_template(seed, dtype)
            self._templates[dt_name] = [tpl, mk]
            return tpl
        tpl, bound = slot
        if csr is not None and mk != bound:
            tpl = tpl.update_values(csr, pattern=self.key)
            self._templates[dt_name] = [tpl, mk]
        return tpl

    def _build_template(self, csr: SparseCSR, dtype):
        return _run_untraced(lambda: self._build_template_eager(csr, dtype))

    def _build_template_eager(self, csr: SparseCSR, dtype):
        if self.mesh is not None:
            from ..dist.operator import _build_sharded_operator

            return _build_sharded_operator(csr, self.mesh, self.axis,
                                           format=self.format, dtype=dtype,
                                           shared=self._shared)
        from ..core.spmv import _build_operator

        op = _build_operator(csr, self.format, dtype, shared=self._shared,
                             context=self.context)
        if op.tuning is None:
            op = dataclasses.replace(op, tuning=self.tuning)
        return op

    def _any_template(self):
        if self._templates:
            return next(iter(self._templates.values()))[0]
        return self._template_for(self._default_dtype())

    # ---- value maps (probed from the format's own refill hook) -------------

    def _refill_container(self, tpl, data: np.ndarray):
        """The format's value-refill applied to the template container with
        ``data`` as the per-nnz values (f32 tables; structure shared)."""
        import jax.numpy as jnp

        csr = SparseCSR(self.n, self.pattern.indptr, self.pattern.indices,
                        np.asarray(data, np.float64))
        if self.mesh is not None:
            from ..dist.operator import _refill_shards

            e_new = tpl.host_ehyb.refill(csr.data)
            return _refill_shards(tpl.obj, e_new, tpl.plan, jnp.float32,
                                  self.mesh, self.axis)
        from .. import autotune as at

        spec = at.get_format(self.format)
        if spec.refill is None:
            raise RuntimeError(f"format {self.format!r} has no refill hook; "
                               f"traceable bind is unavailable")
        return spec.refill(tpl.obj, csr, jnp.float32, {})

    def _raw_apply(self, tpl=None):
        """The format's original-space ``(obj, x) -> y`` closure, wrapped in
        the reliability guard: off the TPU (or under fault injection) a
        Pallas lowering/compile failure downgrades to the reference apply
        at host dispatch instead of crashing; on the TPU it raises.  Sharded plans dispatch
        inside shard_map and keep the unguarded closure."""
        tpl = tpl or self._any_template()
        if self.is_sharded:
            return tpl.apply
        from ..reliability.guard import guarded_apply

        return guarded_apply(self, tpl, "apply")

    def _raw_apply_permuted(self, tpl=None):
        tpl = tpl or self._any_template()
        if tpl.apply_permuted is None or self.is_sharded:
            return tpl.apply_permuted
        from ..reliability.guard import guarded_apply

        return guarded_apply(self, tpl, "permuted")

    @property
    def degraded(self) -> dict:
        """Non-primary guard resolutions, ``{kind: level_name}`` — empty
        when every apply runs its native level (or none resolved yet)."""
        out = {}
        for kind, g in self._guards.items():
            if g.level is not None and g.chain and g.level != g.chain[0]:
                out[kind] = g.level
        return out

    def _ensure_value_maps(self) -> None:
        if self._maps is not None:
            return
        _run_untraced(self._probe_value_maps)

    def _probe_value_maps(self) -> None:
        import jax

        nnz = self.nnz
        if 2 * nnz + 1 >= 2 ** 24:
            raise RuntimeError(
                "value-map probing uses exact f32 integer labels; "
                f"nnz={nnz} exceeds the 2^23 label budget")
        tpl = self._any_template()
        probe1 = np.arange(1, nnz + 1, dtype=np.float64)
        probe2 = probe1 + nnz
        o1 = self._refill_container(tpl, probe1)
        o2 = self._refill_container(tpl, probe2)
        l0, treedef = jax.tree_util.tree_flatten(tpl.obj)
        l1 = jax.tree_util.tree_flatten(o1)[0]
        l2 = jax.tree_util.tree_flatten(o2)[0]
        maps: List = []
        for a1, a2 in zip(l1, l2):
            a1h, a2h = np.asarray(a1), np.asarray(a2)
            if not np.issubdtype(a1h.dtype, np.floating):
                maps.append(None)
                continue
            f1 = np.asarray(a1h, np.float64).ravel()
            f2 = np.asarray(a2h, np.float64).ravel()
            diff = f1 != f2
            if not diff.any():
                maps.append(None)
                continue
            dst = np.flatnonzero(diff)
            src = np.rint(f1[dst]).astype(np.int64) - 1
            ok = ((src >= 0).all() and (src < nnz).all()
                  and np.array_equal(
                      np.rint(f2[dst]).astype(np.int64) - 1 - nnz, src)
                  and not f1[~diff].any())
            if not ok:
                raise RuntimeError(
                    f"format {self.format!r}: value tables are not a "
                    f"zero-backed per-slot selection of the nnz values; "
                    f"in-graph bind/differentiation unavailable")
            maps.append({"dst": dst, "src": src, "shape": a1h.shape,
                         "size": f1.size})
        # which value leaves actually feed the apply (e.g. EHYBDevice keeps
        # a global er_vals copy for the dist path that the fused apply never
        # reads — its cotangent must stay zero or value grads double-count):
        # re-run the apply with each value leaf zeroed; an unread leaf
        # reproduces y bitwise (identical program, identical inputs)
        import jax.numpy as jnp

        # the UNguarded native apply on purpose: the guard's reference level
        # calls back into these value maps (recursion), and a chaos-degraded
        # level must not leak into active-leaf detection
        raw = tpl.apply
        rng = np.random.default_rng(0)
        x = np.asarray(rng.standard_normal(self.n), np.float32)
        y_full = np.asarray(raw(o1, x))
        active: List = []
        for i, vm in enumerate(maps):
            if vm is None:
                active.append(False)
                continue
            lz = list(l1)
            lz[i] = jnp.zeros_like(l1[i])
            y_z = np.asarray(raw(jax.tree_util.tree_unflatten(treedef, lz),
                                 x))
            active.append(not np.array_equal(y_z, y_full))
        covered = np.zeros(nnz, bool)
        recovery: List = []
        for i, vm in enumerate(maps):
            if vm is None:
                continue
            take = ~covered[vm["src"]]
            if take.any():
                recovery.append((i, vm["dst"][take], vm["src"][take]))
                covered[vm["src"][take]] = True
        if not covered.all():
            raise RuntimeError(
                f"format {self.format!r}: {int((~covered).sum())} of "
                f"{nnz} values have no stored slot; cannot recover values")
        self._maps, self._active, self._recovery = maps, active, recovery
        self._treedef = treedef

    def _bind_traced(self, values, dtype) -> "LinearOperator":
        import jax
        import jax.numpy as jnp

        from .operator import LinearOperator

        self._ensure_value_maps()
        tpl = self._any_template()
        leaves, treedef = jax.tree_util.tree_flatten(tpl.obj)
        vals = jnp.asarray(values).astype(dtype)
        new = []
        for leaf, vm in zip(leaves, self._maps):
            if vm is None:
                new.append(leaf)
            else:
                flat = jnp.zeros((vm["size"],), dtype)
                flat = flat.at[vm["dst"]].set(vals[vm["src"]])
                new.append(flat.reshape(vm["shape"]))
        obj = jax.tree_util.tree_unflatten(treedef, new)
        op = LinearOperator(plan=self, obj=obj)
        op._dtype = jnp.dtype(dtype)
        return op

    def values_of(self, obj):
        """Recover the canonical per-nnz value array from a bound container
        (gathers through the probed value maps; trace-safe)."""
        import jax
        import jax.numpy as jnp

        self._ensure_value_maps()
        leaves = jax.tree_util.tree_flatten(obj)[0]
        dt = jnp.result_type(*(leaves[i].dtype for i, _, _ in
                               self._recovery))
        out = jnp.zeros((self.nnz,), dt)
        for i, dst, src in self._recovery:
            out = out.at[src].set(leaves[i].ravel()[dst].astype(dt))
        return out

    # ---- pattern derivatives ----------------------------------------------

    def coo(self) -> Tuple[np.ndarray, np.ndarray]:
        """Per-nnz (rows, cols) of the pattern in CSR order (host arrays)."""
        if self._coo is None:
            rows = np.repeat(np.arange(self.n, dtype=np.int64),
                             self.pattern.row_lengths())
            self._coo = (rows, self.pattern.indices.astype(np.int64))
        return self._coo

    def transpose_order(self) -> np.ndarray:
        """``t_order`` with ``A.T.data == A.data[t_order]`` (CSR order)."""
        if self._t_order is None:
            rows, cols = self.coo()
            self._t_order = np.lexsort((rows, cols))
        return self._t_order

    @property
    def transpose(self) -> "Plan":
        """The plan of the transposed pattern (lazy; shares the plan cache,
        so a structurally symmetric pattern — the FEM norm — resolves to a
        cache hit rather than a second partitioning pass)."""
        rows, cols = self.coo()
        t = self.transpose_order()
        from ..core.matrices import from_coo

        tp = from_coo(self.n, cols[t], rows[t].astype(np.int32),
                      self.pattern.data[t], sum_duplicates=False)
        cache = self.cache or PLAN_CACHE
        return cache.plan_for(tp, self.mesh, self.axis, self.execution)

    # ---- properties --------------------------------------------------------

    def identity(self) -> tuple:
        """The plan's complete decision tuple: pattern hash, chosen format,
        context, partition strategy, execution token, tuned-parameter token.
        A warm (store-served) plan must be bit-identical here to the cold
        plan that persisted it — pinned by the persistence tests."""
        return (self.key, self.format, self.context, self.partition_strategy,
                self.execution.token(),
                None if self.tuned is None else self.tuned.token())

    @property
    def is_sharded(self) -> bool:
        return self.mesh is not None

    @property
    def host_build(self):
        """The shared host EHYB build, when the chosen format has one."""
        return self._shared.get("ehyb")

    def __repr__(self):
        where = f", mesh[{self.axis}]" if self.mesh is not None else ""
        part = (f", partition={self.partition_strategy!r}"
                if self.partition_strategy else "")
        return (f"Plan(n={self.n}, nnz={self.nnz}, format={self.format!r}, "
                f"context={self.context!r}{part}{where}, key={self.key})")
