"""The autotuner: model-ranked, optionally measured, pattern-hash cached.

``autotune(A)`` is OSKI's tuning loop adapted to this framework:

1. **cost-model pass** — rank every registered format by modeled HBM bytes
   (``cost.rank_formats``; one shared EHYB host build serves the family);
2. **measured pass** (``mode="measure"``) — build the ``top_k`` model-ranked
   candidates and time their jitted SpMV on the current backend, picking the
   fastest.  Pallas kernels are skipped on CPU (interpreted there, their
   timings say nothing about device performance); on the TPU a Pallas
   candidate that fails to compile raises instead of being skipped;
3. **cache** — the decision is memoized under (pattern hash, dtype, mode,
   candidate set): re-tuning the same sparsity pattern is a dict lookup, and
   a fixed pattern hash always yields the same selection (pinned by
   tests/test_autotune.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

import numpy as np

from ..core.cache import BoundedCache
from ..core.matrices import SparseCSR
from .cost import pattern_hash, rank_formats


@dataclasses.dataclass(frozen=True)
class TuneResult:
    format: str                       # the winner
    key: str                          # sparsity-pattern hash
    mode: str                         # "model" | "measure"
    modeled_bytes: Dict[str, int]     # per-candidate modeled HBM bytes
    measured_s: Optional[Dict[str, float]]  # per-timed-candidate seconds
    context: str = "spmv"             # workload the model ranked for
    # calibrated predicted seconds per candidate (None when no calibration
    # model is installed for the current backend — ranking fell back to raw
    # modeled bytes)
    calibrated_s: Optional[Dict[str, float]] = None
    # winning tunable-kernel-parameter assignment (TunedParams payload) from
    # the measured sweep, or None when no sweep ran for the winner
    tuned: Optional[Dict[str, int]] = None
    # measured seconds per swept assignment, keyed by TunedParams.token()
    sweep_s: Optional[Dict[tuple, float]] = None


@dataclasses.dataclass(eq=False)
class PartitionTuneResult:
    """``autotune_partition`` outcome: the priced strategy table plus the
    winning :class:`~repro.core.Partition` itself (so the caller builds the
    selected EHYB without re-partitioning)."""

    strategy: str                        # the winner
    key: str                             # sparsity-pattern hash
    context: str                         # workload the model priced for
    n_dev: int                           # mesh size (1 = local)
    modeled_bytes: Dict[str, int]        # per-strategy modeled bytes/SpMV
    in_part_fraction: Dict[str, float]   # per-strategy cached-read share
    halo_words: Dict[str, int]           # per-strategy (dist context only)
    partition: object = dataclasses.field(repr=False, default=None)


_CACHE = BoundedCache(maxsize=128)    # TuneResults are small host dicts
_PART_CACHE = BoundedCache(maxsize=64)  # winners keep their Partition arrays


def clear_cache() -> None:
    _CACHE.clear()
    _PART_CACHE.clear()


def tune_cache_info() -> dict:
    """In-memory tune-cache contents plus the persistent store's counters
    (``disk`` is None when no store is active) — surfaced through
    ``repro.api.PLAN_CACHE.stats()``."""
    from ..tuning.store import get_store

    st = get_store()
    return {"entries": len(_CACHE),
            "keys": sorted(k[0] for k in _CACHE.keys()),
            "disk": None if st is None else st.stats()}


def _time_spmv(apply, obj, x, repeats: int = 5, warmup: int = 1,
               min_duration_s: float = 1e-3, max_inner: int = 512) -> float:
    """Median seconds of one ``apply(obj, x)``.

    Every repeat is explicitly ``block_until_ready``-synced (dispatch alone
    is not a measurement), and each repeat runs an inner loop sized so it
    spans at least ``min_duration_s`` — a sub-millisecond apply timed as a
    single call sits at the clock/dispatch noise floor, which is exactly
    where format rankings flip run to run.  Bumps the ``tune.measured``
    counter once per call: warm-start paths assert that count stays zero.
    """
    import math

    import jax

    from ..core.counters import bump

    bump("tune.measured")
    for _ in range(warmup):
        jax.block_until_ready(apply(obj, x))
    t0 = time.perf_counter()
    jax.block_until_ready(apply(obj, x))
    probe = time.perf_counter() - t0          # also one more warmup rep
    inner = min(max(1, math.ceil(min_duration_s / max(probe, 1e-9))),
                max_inner)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            jax.block_until_ready(apply(obj, x))
        ts.append((time.perf_counter() - t0) / inner)
    return float(np.median(ts))


def autotune(m: SparseCSR, dtype=None, *, mode: str = "model",
             candidates=None, top_k: int = 3, use_cache: bool = True,
             shared: Optional[dict] = None,
             context: str = "spmv", n_dev: int = 1,
             k: int = 1, tuned=None,
             sweep_params: Optional[bool] = None) -> TuneResult:
    """Select the SpMV format for ``m``; see module docstring for the passes.

    ``shared`` (optional dict) carries the host EHYB build across the cost
    model, the measured pass, and the caller's subsequent ``build_format`` —
    one partitioning pass end to end.

    ``context`` selects the workload the byte model ranks for: "spmv"
    (one-shot original-space call), "solver" (permuted-space hot-loop
    iteration; EHYB-family candidates drop the per-call permutation round
    trip), or "dist" (one iteration sharded over ``n_dev`` devices:
    compute bytes plus the interconnect term — halo words for shardable
    formats, the all-gather penalty otherwise) — see ``cost.py``.  The
    measured pass matches: with ``context="solver"`` it times the
    permuted-space apply on a permuted-space vector for formats that
    support it, the operation the hot loop actually runs; with
    ``context="dist"`` the measured pass is skipped and the ranking stays
    model-driven — a single-device timing contains zero interconnect
    traffic, the very term this context prices.  Decisions are cached
    per context (and per ``n_dev`` for "dist").

    ``k`` is the rhs batch width the apply will run at (SpMM).  The byte
    model scales its x/y-sided terms ×k while A-sided streams stay fixed,
    so the ranking can flip as k grows — the SpMM crossover; the measured
    pass times an (n, k) rhs to match.  Decisions are cached per k.

    ``tuned`` (a :class:`repro.tuning.TunedParams`) pins the tunable kernel
    parameters for every candidate build; ``None`` leaves them to the
    measured sweep.  ``sweep_params`` controls that sweep — after the
    measured pass picks a winner, its declared parameter grid
    (:func:`repro.tuning.sweep_grid`) is built and timed and the fastest
    assignment is recorded in ``TuneResult.tuned``.  Default: sweep exactly
    when ``mode="measure"``, the context is measurable (not "dist"), and no
    ``tuned`` pin was given.

    When a calibration model is installed for the current backend
    (:func:`repro.tuning.calibration.get_model`), candidates are ranked by
    **calibrated predicted seconds** (per-term bandwidth coefficients +
    per-format dispatch intercepts) instead of raw modeled bytes; the
    prediction table lands in ``TuneResult.calibrated_s`` and the model's
    fingerprint joins the cache key, so installing or refreshing a
    calibration never serves stale decisions.
    """
    import jax
    import jax.numpy as jnp

    from ..tuning import calibration as _calibration
    from ..tuning.params import TunedParams, sweep_grid
    from .cost import CONTEXTS
    from .registry import available_formats, get_format

    if mode not in ("model", "measure"):
        raise ValueError(f"mode must be 'model' or 'measure', got {mode!r}")
    if context not in CONTEXTS:
        raise ValueError(f"context must be one of {CONTEXTS}, "
                         f"got {context!r}")
    if context == "dist" and n_dev < 2:
        raise ValueError("context='dist' prices a multi-device mesh; "
                         "pass n_dev >= 2 (a 1-device build is "
                         "context='solver')")
    if not isinstance(k, int) or k < 1:
        raise ValueError(f"k must be a positive int, got {k!r}")
    dtype = dtype or jnp.float32
    cand = tuple(candidates or available_formats())
    key = pattern_hash(m)
    cal = _calibration.get_model()
    sweep = ((mode == "measure" and context != "dist" and tuned is None)
             if sweep_params is None else bool(sweep_params))
    cache_key = (key, jnp.dtype(dtype).name, mode, cand, context,
                 n_dev if context == "dist" else None, k,
                 None if tuned is None else tuned.token(), sweep,
                 None if cal is None else cal.fingerprint())
    # rankings decided under fault injection must not outlive it (nor may a
    # clean cached ranking mask an injected failure a test wants to observe)
    from ..reliability.chaos import active as _chaos_active
    from ..reliability.chaos import check_kernel as _chaos_check

    use_cache = use_cache and _chaos_active() is None
    if use_cache and cache_key in _CACHE:
        return _CACHE[cache_key]

    shared = {} if shared is None else shared
    if context == "dist":
        shared["n_dev"] = n_dev
    if tuned is not None:
        shared["tuned"] = tuned
    val_bytes = jnp.dtype(dtype).itemsize
    ranked = rank_formats(m, val_bytes, cand, shared, context, k)
    modeled = dict(ranked)
    calibrated = None
    if cal is not None:
        from .cost import estimate_terms, matrix_stats

        stats = matrix_stats(m)
        calibrated = {f: cal.predict(
            estimate_terms(m, f, val_bytes, shared, stats, context, k), f)
            for f in cand}
        ranked = sorted(calibrated.items(), key=lambda kv: (kv[1], kv[0]))
    # the winner must be executable efficiently on the current backend:
    # interpreter-backed kernels are ranked (their modeled bytes are the TPU
    # story) but never *selected* on CPU, where they would run in Python
    on_cpu = jax.default_backend() == "cpu"
    eligible = [f for f, _ in ranked
                if not (on_cpu and get_format(f).kernel != "xla")]
    winner = (eligible or [ranked[0][0]])[0]
    measured = None

    # dist rankings stay model-driven even under mode="measure": a
    # single-device timing contains zero interconnect traffic, so letting
    # it override the winner would erase exactly the term this context
    # exists to price
    if mode == "measure" and context != "dist":
        timed = eligible[:top_k]
        if timed:
            rng0 = np.random.default_rng(0)
            shape = (m.n,) if k == 1 else (m.n, k)
            x = jnp.asarray(rng0.standard_normal(shape), dtype=dtype)
            measured = {}
            for f in timed:
                # a candidate whose build/compile/run fails (organically or
                # chaos-injected) is skipped, not fatal — the measured pass
                # ranks whatever actually executes on this backend; only a
                # Pallas candidate on the TPU (no chaos armed) raises
                spec = get_format(f)
                try:
                    _chaos_check(f"tune:{f}")
                    obj, apply = spec.build(m, dtype, shared)
                    if context == "solver" and spec.permuted is not None:
                        # time what the solver loop actually runs: the
                        # permuted-space apply on a permuted-space vector —
                        # the original-space apply's per-call perm round
                        # trip would pollute exactly the timings this
                        # context ranks on
                        pshape = (obj.n_pad,) if k == 1 else (obj.n_pad, k)
                        xp = jnp.asarray(rng0.standard_normal(pshape),
                                         dtype=dtype)
                        measured[f] = _time_spmv(spec.permuted, obj, xp)
                    else:
                        measured[f] = _time_spmv(apply, obj, x)
                except Exception as e:    # noqa: BLE001 — any kernel error
                    if (spec.kernel != "xla" and _chaos_active() is None
                            and jax.default_backend() == "tpu"):
                        raise     # the chip's Pallas path must not vanish
                    import warnings

                    from ..core.counters import bump
                    from ..reliability.policy import ReliabilityWarning

                    bump("tune.candidate_failed")
                    warnings.warn(
                        f"autotune: measured candidate {f!r} failed "
                        f"({type(e).__name__}: {e}); skipping it",
                        ReliabilityWarning, stacklevel=2)
            if measured:
                winner = min(sorted(measured), key=measured.get)

    # ---- tunable-kernel-parameter sweep for the winner --------------------
    # (measured contexts only: parameter choice is a timing decision, and a
    # "dist" plan has no single-device timing worth listening to)
    best = tuned
    sweep_s = None
    if sweep and mode == "measure" and context != "dist":
        spec = get_format(winner)
        grid = list(sweep_grid(winner, k=k))
        if len(grid) > 1 and not (on_cpu and spec.kernel != "xla"):
            rng0 = np.random.default_rng(1)
            shape = (m.n,) if k == 1 else (m.n, k)
            x = jnp.asarray(rng0.standard_normal(shape), dtype=dtype)
            sweep_s = {}
            for params in grid:
                try:
                    _chaos_check(f"tune:{winner}:sweep")
                    sh = dict(shared)
                    sh["tuned"] = params
                    obj, apply = spec.build(m, dtype, sh)
                    if context == "solver" and spec.permuted is not None:
                        pshape = (obj.n_pad,) if k == 1 else (obj.n_pad, k)
                        xp = jnp.asarray(rng0.standard_normal(pshape),
                                         dtype=dtype)
                        sweep_s[params.token()] = _time_spmv(spec.permuted,
                                                             obj, xp)
                    else:
                        sweep_s[params.token()] = _time_spmv(apply, obj, x)
                except Exception as e:    # noqa: BLE001 — same rule as the
                    # candidate loop above: a swept assignment that fails to
                    # build/compile/run is skipped, never fatal
                    import warnings

                    from ..core.counters import bump
                    from ..reliability.policy import ReliabilityWarning

                    bump("tune.candidate_failed")
                    warnings.warn(
                        f"autotune: swept params {params.to_dict()} for "
                        f"{winner!r} failed ({type(e).__name__}: {e}); "
                        f"skipping", ReliabilityWarning, stacklevel=2)
            if sweep_s:
                best_tok = min(sorted(sweep_s), key=sweep_s.get)
                best = TunedParams(**dict(best_tok))

    result = TuneResult(format=winner, key=key, mode=mode,
                        modeled_bytes=modeled, measured_s=measured,
                        context=context, calibrated_s=calibrated,
                        tuned=None if best is None else best.to_dict(),
                        sweep_s=sweep_s)
    if use_cache:
        _CACHE[cache_key] = result
    return result


def autotune_partition(m: SparseCSR, *, candidates=None,
                       context: str = "spmv", n_dev: int = 1,
                       val_bytes: int = 4,
                       use_cache: bool = True) -> PartitionTuneResult:
    """Pick the partition strategy the bytes-moved model prefers for ``m``.

    Builds every registered strategy's partition (at the standard
    ``choose_vec_size`` geometry, the one ``build_ehyb`` uses) and prices
    each with :func:`~repro.autotune.cost.partition_cost` in the requested
    workload context — locally that ranking is exactly ELL-width padding +
    ER spill + in-partition fraction, for ``context="dist"`` it adds the
    scheduled halo words over ``n_dev`` devices.  Ties break toward the
    higher in-partition fraction, then the name, so selection is
    deterministic.

    One guardrail sits on top of the byte ranking: whenever ``natural`` is
    among the candidates, the winner must serve at least as large a share of
    x-reads from the explicit cache as ``natural`` does (the paper's primary
    locality metric).  Tile padding can make the byte model elect a
    partition that caches *fewer* reads than no reordering at all — e.g. a
    hub extraction whose narrow ELL tile wins on modeled bytes while its
    cached-read share collapses — and that floor strikes such candidates
    (``natural`` itself always clears it, so the eligible set is never
    empty).  Decisions are cached under the sparsity-pattern hash the
    same way format autotuning is; ``plan()`` runs this when the execution
    config leaves ``partition_method`` unset.
    """
    from ..core.partition import (available_strategies, choose_vec_size,
                                  make_partition)
    from .cost import CONTEXTS, partition_cost

    if context not in CONTEXTS:
        raise ValueError(f"unknown context {context!r}; have {CONTEXTS}")
    if context == "dist" and n_dev < 2:
        raise ValueError("context='dist' needs n_dev >= 2")
    cand = tuple(candidates) if candidates else available_strategies()
    key = pattern_hash(m)
    cache_key = (key, cand, context, n_dev if context == "dist" else 1,
                 val_bytes)
    if use_cache and cache_key in _PART_CACHE:
        return _PART_CACHE[cache_key]

    # partition geometry is the build-time default (dtype_bytes=4) so the
    # winner drops straight into build_ehyb; val_bytes only weights pricing
    n_parts, vec_size = choose_vec_size(m.n)
    modeled: Dict[str, int] = {}
    fracs: Dict[str, float] = {}
    halos: Dict[str, int] = {}
    parts = {}
    for name in cand:
        part = make_partition(m, method=name, n_parts=n_parts,
                              vec_size=vec_size)
        cost = partition_cost(m, part, val_bytes, context=context,
                              n_dev=n_dev)
        modeled[name] = cost["total"]
        fracs[name] = part.in_partition_fraction(m)
        if context == "dist":
            halos[name] = cost["interconnect"] // (val_bytes or 1)
        parts[name] = part
    # cached-read-share floor (see docstring): rank by modeled bytes, but
    # never regress the in-partition fraction below the natural baseline
    floor = fracs.get("natural", float("-inf")) - 1e-12
    eligible = [s for s in cand if fracs[s] >= floor] or list(cand)
    winner = min(eligible, key=lambda s: (modeled[s], -fracs[s], s))
    result = PartitionTuneResult(strategy=winner, key=key, context=context,
                                 n_dev=n_dev, modeled_bytes=modeled,
                                 in_part_fraction=fracs, halo_words=halos,
                                 partition=parts[winner])
    if use_cache:
        _PART_CACHE[cache_key] = result
    return result
