"""Format registry: every device SpMV format behind one uniform interface.

A :class:`FormatSpec` bundles the three things the framework needs to treat a
format as a candidate:

* ``build(m, dtype, shared)``  — construct the device container and return
  ``(obj, apply)`` with ``apply(obj, x)`` the jitted SpMV/SpMM path;
* ``model(m, stats, val_bytes, shared, context=...)`` — modeled HBM bytes of
  one SpMV in that format (the paper's §3.4 accounting), computable from the
  sparsity pattern alone — no device arrays are allocated for losers.
  ``context`` distinguishes one-shot original-space calls ("spmv") from
  permuted-space solver iterations ("solver") — see ``cost.py``;
* ``kernel`` — which execution engine backs it ("xla" or "pallas").  A
  Pallas kernel is compiled on the TPU and interpreted on CPU, so the
  tuner never selects or times it on CPU, where its timings say nothing;
* ``permuted`` — optional ``apply_permuted(obj, x_new)`` running the SpMV in
  the format's reordered padded space (EHYB family), the hook behind
  ``SpMVOperator.matvec_permuted`` and the permuted-space solver loop;
* ``refill`` — ``refill(obj, m_new, dtype, shared)``: rebuild only the value
  tables of an existing device container for a matrix with the *same
  sparsity pattern* but new entry values, returning a container with the
  identical pytree structure (structural arrays shared by reference, jitted
  applies hit the existing XLA cache).  Trivial for the unpartitioned
  formats; plan-driven (zero partitioning/packing passes) for the EHYB
  family.  The hook behind ``SpMVOperator.update_values`` — any future
  format that provides it inherits the whole value-refresh fast path;
* ``shard`` — ``shard(op, mesh, axis, csr=None)``: lift a built operator
  onto a device mesh as a :class:`repro.dist.ShardedOperator` (halo-plan
  exchange, distributed solve, sharded refills).  EHYB-family only — the
  hook is what makes a format *distributable*, and its presence is what
  the ``context="dist"`` cost model keys the interconnect term on
  (formats without it pay the all-gather penalty in the dist ranking and
  are excluded from ``build_sharded_spmv``'s candidate set).

The EHYB-family formats share one host-side EHYB build per matrix via the
``shared`` dict (allocated per autotune/build call), so ranking all six
candidates costs one partitioning pass, not three.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import numpy as np

from ..core.ehyb import (EHYB, build_buckets, build_ehyb,
                         group_er_by_partition, pack_staircase)
from ..core.matrices import SparseCSR
from ..core.spmv import (COODevice, EHYBBucketsDevice, EHYBDevice,
                         EHYBPackedDevice, ELLDevice, HYBDevice, coo_spmv,
                         ehyb_buckets_spmv, ehyb_buckets_spmv_permuted,
                         ehyb_spmv, ehyb_spmv_buckets, ehyb_spmv_permuted,
                         ell_spmv, hyb_spmv)
from .cost import MatrixStats, _x_stream_bytes


@dataclasses.dataclass(frozen=True)
class FormatSpec:
    name: str
    build: Callable[..., tuple]        # (m, dtype, shared) -> (obj, apply)
    model: Callable[..., int]          # (m, stats, vb, shared, context, k)->B
    kernel: str = "xla"                # "xla" | "pallas"
    description: str = ""
    permuted: Optional[Callable] = None   # (obj, x_new) -> y_new, or None
    refill: Optional[Callable] = None     # (obj, m_new, dtype, shared) -> obj
    shard: Optional[Callable] = None      # (op, mesh, axis, csr) -> Sharded
    # per-term byte breakdown along cost.TERMS (same accounting as ``model``,
    # split by traffic kind) — the calibration layer's feature vector.  None
    # = the whole model collapses into the sequential-stream term.
    terms: Optional[Callable] = None
    # static verification hook (analysis.invariants): ``invariants(obj) ->
    # list[Finding]`` checks the format's structural invariants on a built
    # device container — index bounds, permutation bijectivity, staircase
    # monotonicity, padding discipline.  ``repro.analysis.verify`` routes
    # operators through it, so a format registered with a hook is covered
    # by ``Plan.bind(validate="full")``, ``benchmarks/run.py --verify`` and
    # the corruption regression suite without touching the verifier.
    invariants: Optional[Callable] = None


FORMATS: Dict[str, FormatSpec] = {}


def register_format(spec: FormatSpec) -> FormatSpec:
    if spec.name in FORMATS:
        raise ValueError(f"format {spec.name!r} already registered")
    FORMATS[spec.name] = spec
    return spec


def get_format(name: str) -> FormatSpec:
    try:
        return FORMATS[name]
    except KeyError:
        raise KeyError(f"unknown SpMV format {name!r}; "
                       f"registered: {sorted(FORMATS)}") from None


def available_formats() -> list[str]:
    return sorted(FORMATS)


def build_format(name: str, m: SparseCSR, dtype=None,
                 shared: Optional[dict] = None) -> tuple:
    """Build ``name``'s device container for ``m``; returns (obj, apply)."""
    import jax.numpy as jnp

    return get_format(name).build(m, dtype or jnp.float32, shared or {})


# ---------------------------------------------------------------------------
# shared host-side EHYB build (one partitioning pass for the whole family)
# ---------------------------------------------------------------------------

def shared_ehyb(m: SparseCSR, shared: dict) -> EHYB:
    """Host EHYB for ``m``: per-call ``shared`` dict first, then the host
    memo of the Operator API v2 plan cache (``repro.api.PLAN_CACHE`` —
    which replaced the ``_HOST_EHYB``/``_HOST_EHYB_PATTERN`` globals that
    used to live here), so the cost model, the device builders, and any
    caller asking for stats all reuse one partitioning pass per matrix.

    The memo is two-level: an exact (value-inclusive) hit returns the build
    as-is, and a *pattern* hit — same ``indptr``/``indices``, new values —
    refills the cached build's value tables through its recorded scatter
    plan instead of re-partitioning (the §6 amortization: structure cost is
    paid per pattern, not per value update)."""
    if "ehyb" not in shared:
        from ..api.plan import PLAN_CACHE

        shared["ehyb"] = PLAN_CACHE.host_ehyb(m)
    return shared["ehyb"]


def _tuned_n_buckets(shared: dict) -> int:
    """The bucketed format's width-class count for this build: the tuned
    value when the caller planned one (``shared["tuned"]``), else the
    ``build_buckets`` default."""
    tuned = shared.get("tuned")
    return tuned.n_buckets if tuned is not None else 4


def memo_buckets(e: EHYB, n_buckets: int = 4):
    """Bucketed view of a host EHYB build, memoized per bucket count.

    The default count lives in the ``_buckets`` slot (the one
    ``EHYB.refill`` carries across value refreshes); tuned non-default
    counts memoize in the sibling ``_buckets_nb`` dict, also refill-
    propagated, so a tuned plan's rebinds never re-bucket either."""
    if n_buckets == 4:
        b = getattr(e, "_buckets", None)
        if b is None:
            b = e._buckets = build_buckets(e)
        return b
    memo = getattr(e, "_buckets_nb", None)
    if memo is None:
        memo = e._buckets_nb = {}
    b = memo.get(n_buckets)
    if b is None:
        b = memo[n_buckets] = build_buckets(e, n_buckets=n_buckets)
    return b


def shared_buckets(m: SparseCSR, shared: dict):
    """Width-bucketed view of the shared EHYB build, memoized on the host
    EHYB instance — the cost model and the device builder reuse one
    bucketing pass (it copies every ELL tile, so rebuilding per model
    evaluation is measurable on large matrices).  The bucket count follows
    ``shared["tuned"]`` (a :class:`repro.tuning.TunedParams`) when set."""
    return memo_buckets(shared_ehyb(m, shared), _tuned_n_buckets(shared))


def shared_packed(m: SparseCSR, shared: dict):
    """Packed-staircase view of the shared EHYB build, memoized on the host
    EHYB instance — repeated packed builds (and value refills, which replay
    the recorded pack scatter) reuse one packing pass."""
    e = shared_ehyb(m, shared)
    pk = getattr(e, "_packed", None)
    if pk is None:
        pk = e._packed = pack_staircase(e)
    return pk


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def _build_csr(m, dtype, shared):
    return COODevice.from_csr(m, dtype), coo_spmv


def _build_ell(m, dtype, shared):
    return ELLDevice.from_csr(m, dtype), ell_spmv


def _build_hyb(m, dtype, shared):
    return HYBDevice.from_csr(m, dtype), hyb_spmv


def _build_ehyb(m, dtype, shared):
    e = shared_ehyb(m, shared)
    obj = EHYBDevice.from_ehyb(e, dtype)
    obj.host_ehyb = e                 # refill provenance (not pytree state)
    return obj, ehyb_spmv


def _build_ehyb_bucketed(m, dtype, shared):
    b = shared_buckets(m, shared)
    return EHYBBucketsDevice.from_buckets(b, dtype), ehyb_buckets_spmv


def _build_ehyb_packed(m, dtype, shared):
    from ..kernels.ops import ehyb_spmv_packed_pallas

    pk = shared_packed(m, shared)
    tuned = shared.get("tuned")
    obj = EHYBPackedDevice.from_packed(
        pk, dtype, kparams=tuned.token() if tuned is not None else ())
    obj.host_packed = pk              # refill provenance (not pytree state)
    return obj, ehyb_spmv_packed_pallas


def _packed_permuted(d, x_new):
    from ..kernels.ops import ehyb_spmv_packed_pallas_permuted

    return ehyb_spmv_packed_pallas_permuted(d, x_new)


def _build_dense(m, dtype, shared):
    import jax.numpy as jnp

    a = jnp.asarray(m.to_dense(), dtype=dtype)
    return a, lambda aa, x: aa @ x


# ---------------------------------------------------------------------------
# value-refresh hooks: same pattern, new values -> same-structure container.
# Every hook returns the old container with ONLY its value leaves replaced
# (``dataclasses.replace`` shares the structural arrays by reference), so the
# refreshed operator hits the jitted applies' existing XLA cache.
# ---------------------------------------------------------------------------

def _csr_scatter(m):
    """(rows, k) position of each CSR entry within its row (k = column slot
    in a row-padded table; callers mask k against their table width)."""
    lens = m.row_lengths()
    rows = np.repeat(np.arange(m.n), lens)
    start = np.concatenate([[0], np.cumsum(lens)])
    k = np.arange(m.nnz) - start[rows]
    return rows, k


def _refill_csr(obj, m, dtype, shared):
    import jax.numpy as jnp

    return dataclasses.replace(obj, vals=jnp.asarray(m.data, dtype=dtype))


def _refill_ell(obj, m, dtype, shared):
    import jax.numpy as jnp

    w = obj.vals.shape[1]
    rows, k = _csr_scatter(m)
    vals = np.zeros((m.n, w))
    vals[rows, k] = m.data
    return dataclasses.replace(obj, vals=jnp.asarray(vals, dtype=dtype))


def _refill_hyb(obj, m, dtype, shared):
    import jax.numpy as jnp

    k_ell = obj.ell_vals.shape[1]     # same pattern -> same ELL/COO split
    rows, k = _csr_scatter(m)
    in_ell = k < k_ell
    vals = np.zeros((m.n, k_ell))
    vals[rows[in_ell], k[in_ell]] = m.data[in_ell]
    return dataclasses.replace(
        obj, ell_vals=jnp.asarray(vals, dtype=dtype),
        coo_vals=jnp.asarray(m.data[~in_ell], dtype=dtype))


def _refill_dense(obj, m, dtype, shared):
    import jax.numpy as jnp

    return jnp.asarray(m.to_dense(), dtype=dtype)


def _refilled_host(m, shared, e_old) -> EHYB:
    """Host EHYB for the new values, aligned with the container's structure.

    Prefers replaying ``e_old``'s scatter plan (guaranteed to match the
    device container, including caller-supplied partitionings that never
    entered the global memo); falls back to the shared two-level memo."""
    if "ehyb" not in shared:
        if e_old is not None and e_old.fill_plan is not None:
            shared["ehyb"] = e_old.refill(m.data)
        else:
            shared_ehyb(m, shared)
    return shared["ehyb"]


def _refill_ehyb(obj, m, dtype, shared):
    import jax.numpy as jnp

    e = _refilled_host(m, shared, getattr(obj, "host_ehyb", None))
    g = group_er_by_partition(e)
    new = dataclasses.replace(
        obj, ell_vals=jnp.asarray(e.ell_vals, dtype=dtype),
        er_vals=jnp.asarray(e.er_vals, dtype=dtype),
        er_p_vals=jnp.asarray(g["er_p_vals"], dtype=dtype))
    new.host_ehyb = e
    return new


def _refill_ehyb_bucketed(obj, m, dtype, shared):
    import jax.numpy as jnp

    b_old = obj.host
    e = _refilled_host(m, shared, b_old.base if b_old is not None else None)
    # rebuild at the container's own bucket count (it may be a tuned,
    # non-default value) — EHYB.refill propagates both memo slots, so this
    # is a dict hit on the refill path, not a re-bucketing pass
    b = memo_buckets(e, len(b_old.vals) if b_old is not None
                     else _tuned_n_buckets(shared))
    g = group_er_by_partition(e)
    return dataclasses.replace(
        obj, vals=tuple(jnp.asarray(v, dtype=dtype) for v in b.vals),
        er_p_vals=jnp.asarray(g["er_p_vals"], dtype=dtype), host=b)


def _refill_ehyb_packed(obj, m, dtype, shared):
    import jax.numpy as jnp

    pk_old = getattr(obj, "host_packed", None)
    e = _refilled_host(m, shared, pk_old.base if pk_old is not None else None)
    pk = getattr(e, "_packed", None)
    if pk is None:
        pk = e._packed = (pk_old.refill(e)
                          if pk_old is not None and pk_old.pack_plan
                          is not None else pack_staircase(e))
    g = group_er_by_partition(e)
    new = dataclasses.replace(
        obj, packed_vals=jnp.asarray(pk.packed_vals, dtype=dtype),
        er_vals=jnp.asarray(e.er_vals, dtype=dtype),
        er_p_vals=jnp.asarray(g["er_p_vals"], dtype=dtype))
    new.host_packed = pk
    return new


# ---------------------------------------------------------------------------
# byte models (one SpMV, fp-width ``val_bytes``); x-stream bounds in cost.py.
# ``context``: "spmv" = one-shot original-space call; "solver" = one
# permuted-space hot-loop iteration (EHYB family drops the perm round trip —
# non-EHYB formats have no reordered space, so their models ignore it).
# ``k``: rhs batch width (SpMM) — A-sided streams are read once, every
# x/y-sided term scales ×k, so formats whose traffic is x/y-light (dense,
# EHYB's exact cache) gain ground on the gather-heavy ones as k grows.
# ---------------------------------------------------------------------------

def _model_csr(m, stats: MatrixStats, vb: int, shared,
               context: str = "spmv", k: int = 1) -> int:
    # COO stream realization of CSR semantics: rows + cols int32 per nnz
    idx = 8 * stats.nnz
    return (idx + vb * stats.nnz
            + k * (_x_stream_bytes(stats, vb) + vb * stats.n))


def _model_ell(m, stats: MatrixStats, vb: int, shared,
               context: str = "spmv", k: int = 1) -> int:
    stored = stats.n * stats.max_row
    return (stored * (vb + 4)
            + k * (_x_stream_bytes(stats, vb) + vb * stats.n))


def _model_hyb(m, stats: MatrixStats, vb: int, shared,
               context: str = "spmv", k: int = 1) -> int:
    lens = m.row_lengths()
    kq = max(int(np.quantile(lens, 0.9)) if stats.n else 1, 1)
    spill = int(np.maximum(lens - kq, 0).sum())
    ell = stats.n * kq * (vb + 4)
    coo = spill * (vb + 8)
    return ell + coo + k * (_x_stream_bytes(stats, vb) + vb * stats.n)


def _ehyb_space(context: str) -> str:
    # solver AND dist iterations run natively permuted (hoisted round trip)
    return "permuted" if context in ("solver", "dist") else "original"


def _ehyb_dist_kw(m, shared, context: str) -> dict:
    """halo_words/n_dev kwargs for ``bytes_moved`` in the dist context —
    the scheduled exchange payload of the matrix's halo plan."""
    if context != "dist":
        return {}
    from ..dist.halo import ehyb_halo_words

    n_dev = int(shared["n_dev"])      # required; estimate_bytes validates
    e = shared_ehyb(m, shared)
    return {"halo_words": ehyb_halo_words(e, n_dev), "n_dev": n_dev}


def _model_ehyb(m, stats, vb, shared, context: str = "spmv",
                k: int = 1) -> int:
    return shared_ehyb(m, shared).bytes_moved(
        vb, layout="tile", space=_ehyb_space(context),
        fused_er=True, k=k, **_ehyb_dist_kw(m, shared, context))["total"]


def _model_ehyb_bucketed(m, stats, vb, shared, context: str = "spmv",
                         k: int = 1) -> int:
    if context == "dist":
        # the shared shard hook executes the BASE uniform-tile apply for
        # the whole family — ranking dist candidates by single-device
        # layout savings the sharded program never realizes would make
        # the "winner" noise (ties then break to plain "ehyb" by name)
        return _model_ehyb(m, stats, vb, shared, context, k)
    return shared_buckets(m, shared).bytes_moved(
        vb, space=_ehyb_space(context), fused_er=True, k=k)["total"]


def _model_ehyb_packed(m, stats, vb, shared, context: str = "spmv",
                       k: int = 1) -> int:
    if context == "dist":
        return _model_ehyb(m, stats, vb, shared, context, k)  # see bucketed
    return shared_ehyb(m, shared).bytes_moved(
        vb, layout="packed", space=_ehyb_space(context),
        fused_er=True, k=k)["total"]


def _model_dense(m, stats, vb, shared, context: str = "spmv",
                 k: int = 1) -> int:
    return stats.n * stats.n * vb + k * 2 * stats.n * vb


# ---------------------------------------------------------------------------
# per-term breakdowns (cost.TERMS axes) — same totals as the models above,
# split by traffic kind so calibration can price sequential streams, cached
# reads, and random gathers separately.  For the unpartitioned formats the
# split is: A-stream -> "ell", uncached x gather -> "er", output -> "y".
# ---------------------------------------------------------------------------

def _terms_csr(m, stats, vb, shared, context="spmv", k=1):
    return {"ell": (8 + vb) * stats.nnz,
            "er": k * _x_stream_bytes(stats, vb),
            "y": k * vb * stats.n}


def _terms_ell(m, stats, vb, shared, context="spmv", k=1):
    return {"ell": stats.n * stats.max_row * (vb + 4),
            "er": k * _x_stream_bytes(stats, vb),
            "y": k * vb * stats.n}


def _terms_hyb(m, stats, vb, shared, context="spmv", k=1):
    lens = m.row_lengths()
    kq = max(int(np.quantile(lens, 0.9)) if stats.n else 1, 1)
    spill = int(np.maximum(lens - kq, 0).sum())
    return {"ell": stats.n * kq * (vb + 4),
            "er": spill * (vb + 8) + k * _x_stream_bytes(stats, vb),
            "y": k * vb * stats.n}


def _terms_dense(m, stats, vb, shared, context="spmv", k=1):
    return {"ell": stats.n * stats.n * vb, "x_cache": k * stats.n * vb,
            "y": k * stats.n * vb}


def _split_bytes_moved(d: dict) -> dict:
    return {t: v for t, v in d.items() if t != "total"}


def _terms_ehyb(m, stats, vb, shared, context="spmv", k=1):
    return _split_bytes_moved(shared_ehyb(m, shared).bytes_moved(
        vb, layout="tile", space=_ehyb_space(context), fused_er=True, k=k,
        **_ehyb_dist_kw(m, shared, context)))


def _terms_ehyb_bucketed(m, stats, vb, shared, context="spmv", k=1):
    if context == "dist":
        return _terms_ehyb(m, stats, vb, shared, context, k)  # see model
    return _split_bytes_moved(shared_buckets(m, shared).bytes_moved(
        vb, space=_ehyb_space(context), fused_er=True, k=k))


def _terms_ehyb_packed(m, stats, vb, shared, context="spmv", k=1):
    if context == "dist":
        return _terms_ehyb(m, stats, vb, shared, context, k)  # see model
    return _split_bytes_moved(shared_ehyb(m, shared).bytes_moved(
        vb, layout="packed", space=_ehyb_space(context), fused_er=True, k=k))


def _invariants_hook(name: str) -> Callable:
    """Default ``invariants`` hook: delegate to the built-in per-format
    checkers in ``repro.analysis.invariants`` (lazy import — the registry
    stays importable without pulling the analysis subsystem)."""
    def run(obj):
        from ..analysis.invariants import format_invariants

        return format_invariants(name, obj)
    return run


register_format(FormatSpec(
    "csr", _build_csr, _model_csr, terms=_terms_csr,
    description="COO/CSR gather + segment-sum stream (paper's baseline)",
    refill=_refill_csr, invariants=_invariants_hook("csr")))
register_format(FormatSpec(
    "ell", _build_ell, _model_ell, terms=_terms_ell,
    description="ELLPACK padded to the global max row width",
    refill=_refill_ell, invariants=_invariants_hook("ell")))
register_format(FormatSpec(
    "hyb", _build_hyb, _model_hyb, terms=_terms_hyb,
    description="classic HYB (Bell & Garland): ELL to 90th pct + COO spill",
    refill=_refill_hyb, invariants=_invariants_hook("hyb")))
def _shard_ehyb(op, mesh, axis, csr=None):
    """The EHYB family's ``shard`` hook: lift onto a mesh via the halo-plan
    subsystem (lazy import — the registry stays importable without jax
    device state).  The sharded program always executes the base
    uniform-tile apply recovered from the host EHYB build — bucketed/packed
    single-device layouts have no sharded kernels (yet), which is also why
    the dist-context models above collapse the family to one ranking."""
    from ..dist.operator import shard_operator

    return shard_operator(op, mesh, axis, csr=csr)


register_format(FormatSpec(
    "ehyb", _build_ehyb, _model_ehyb, terms=_terms_ehyb,
    description="EHYB uniform tiles, uint16 local cols, explicit x cache",
    permuted=ehyb_spmv_permuted, refill=_refill_ehyb, shard=_shard_ehyb,
    invariants=_invariants_hook("ehyb")))
register_format(FormatSpec(
    "ehyb_bucketed", _build_ehyb_bucketed, _model_ehyb_bucketed,
    terms=_terms_ehyb_bucketed,
    description="EHYB with width-bucketed partition tiles",
    permuted=ehyb_buckets_spmv_permuted, refill=_refill_ehyb_bucketed,
    shard=_shard_ehyb, invariants=_invariants_hook("ehyb_bucketed")))
register_format(FormatSpec(
    "ehyb_packed", _build_ehyb_packed, _model_ehyb_packed,
    terms=_terms_ehyb_packed,
    kernel="pallas",
    description="EHYB packed staircase (Pallas kernel, VMEM x-slice cache)",
    permuted=_packed_permuted, refill=_refill_ehyb_packed,
    shard=_shard_ehyb,
    invariants=_invariants_hook("ehyb_packed")))
register_format(FormatSpec(
    "dense", _build_dense, _model_dense, terms=_terms_dense,
    description="dense matmul (wins only on tiny/near-dense matrices)",
    refill=_refill_dense, invariants=_invariants_hook("dense")))
