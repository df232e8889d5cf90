"""JAX SpMV/SpMM paths for every format the paper evaluates.

Baselines (paper §2.2/§5): COO, CSR (scalar + vector semantics collapse to
gather + segment-sum streams under XLA), ELL, classic HYB (Bell & Garland).
The GPU frameworks the paper races (CSR5, merge-based, holaspmv, cuSPARSE
ALG1/2) differ from vanilla CSR only in *scheduling* — warp/thread work
assignment — which XLA:TPU owns; their memory traffic is CSR's.  We therefore
benchmark formats (traffic), and note the scheduling distinction in DESIGN.md.

EHYB is provided both as this pure-jnp path (the oracle for the Pallas kernel,
and itself a deployable XLA path) and as the Pallas kernel in
``repro.kernels`` (VMEM-explicit version).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .ehyb import EHYB, EHYBBuckets, group_er_by_partition
from .matrices import SparseCSR


# ---------------------------------------------------------------------------
# device-side format containers (jnp arrays, pytree-compatible)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class COODevice:
    n: int
    rows: jnp.ndarray
    cols: jnp.ndarray
    vals: jnp.ndarray

    def tree_flatten(self):
        return (self.rows, self.cols, self.vals), (self.n,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(aux[0], *leaves)

    @classmethod
    def from_csr(cls, m: SparseCSR, dtype=jnp.float32):
        rows = np.repeat(np.arange(m.n, dtype=np.int32), m.row_lengths())
        return cls(m.n, jnp.asarray(rows), jnp.asarray(m.indices),
                   jnp.asarray(m.data, dtype=dtype))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ELLDevice:
    n: int
    vals: jnp.ndarray   # (n, W)
    cols: jnp.ndarray   # (n, W) int32 (global)

    def tree_flatten(self):
        return (self.vals, self.cols), (self.n,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(aux[0], *leaves)

    @classmethod
    def from_csr(cls, m: SparseCSR, dtype=jnp.float32):
        lens = m.row_lengths()
        W = max(int(lens.max()) if m.n else 1, 1)
        vals = np.zeros((m.n, W))
        cols = np.zeros((m.n, W), dtype=np.int32)
        rows = np.repeat(np.arange(m.n), lens)
        start = np.concatenate([[0], np.cumsum(lens)])
        k = np.arange(m.nnz) - start[rows]
        vals[rows, k] = m.data
        cols[rows, k] = m.indices
        return cls(m.n, jnp.asarray(vals, dtype=dtype), jnp.asarray(cols))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HYBDevice:
    """Classic HYB (Bell & Garland 2009): ELL up to width K + COO spill."""

    n: int
    ell_vals: jnp.ndarray
    ell_cols: jnp.ndarray
    coo_rows: jnp.ndarray
    coo_cols: jnp.ndarray
    coo_vals: jnp.ndarray

    def tree_flatten(self):
        return ((self.ell_vals, self.ell_cols, self.coo_rows, self.coo_cols,
                 self.coo_vals), (self.n,))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(aux[0], *leaves)

    @classmethod
    def from_csr(cls, m: SparseCSR, dtype=jnp.float32, frac: float = 0.9):
        """K chosen so ≥ ``frac`` of rows fit fully in ELL (standard rule)."""
        lens = m.row_lengths()
        K = max(int(np.quantile(lens, frac)) if m.n else 1, 1)
        rows = np.repeat(np.arange(m.n), lens)
        start = np.concatenate([[0], np.cumsum(lens)])
        k = np.arange(m.nnz) - start[rows]
        in_ell = k < K
        vals = np.zeros((m.n, K))
        cols = np.zeros((m.n, K), dtype=np.int32)
        vals[rows[in_ell], k[in_ell]] = m.data[in_ell]
        cols[rows[in_ell], k[in_ell]] = m.indices[in_ell]
        return cls(m.n, jnp.asarray(vals, dtype=dtype), jnp.asarray(cols),
                   jnp.asarray(rows[~in_ell].astype(np.int32)),
                   jnp.asarray(m.indices[~in_ell]),
                   jnp.asarray(m.data[~in_ell], dtype=dtype))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EHYBDevice:
    """Device-side EHYB (baseline uniform tiles).

    Besides the global ER tables (kept for the distributed path), the
    container carries the ER slots regrouped by owning partition
    (``er_p_*``, built once by :func:`repro.core.ehyb.group_er_by_partition`)
    so the fused kernel — and the jnp oracle mirroring it — accumulate ER
    rows inside the grid step that owns them.  ``has_er`` is static aux so
    jitted paths drop the ER stage entirely on ER-free matrices.
    """

    n: int
    n_pad: int
    n_parts: int
    vec_size: int
    has_er: bool
    ell_vals: jnp.ndarray    # (P, V, W)
    ell_cols: jnp.ndarray    # (P, V, W) uint16 local
    er_vals: jnp.ndarray     # (R, We)
    er_cols: jnp.ndarray     # (R, We) int32 global-new
    er_row_idx: jnp.ndarray  # (R,)
    er_p_vals: jnp.ndarray   # (P, E, We) — ER grouped by owning partition
    er_p_cols: jnp.ndarray   # (P, E, We) int32 global-new
    er_p_rows: jnp.ndarray   # (P, E) int32 local row within the partition
    perm: jnp.ndarray        # (n_pad,)
    inv_perm: jnp.ndarray    # (n_pad,)

    def tree_flatten(self):
        leaves = (self.ell_vals, self.ell_cols, self.er_vals, self.er_cols,
                  self.er_row_idx, self.er_p_vals, self.er_p_cols,
                  self.er_p_rows, self.perm, self.inv_perm)
        return leaves, (self.n, self.n_pad, self.n_parts, self.vec_size,
                        self.has_er)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*aux, *leaves)

    @classmethod
    def from_ehyb(cls, e: EHYB, dtype=jnp.float32):
        t = e.as_jax(dtype=dtype)
        g = group_er_by_partition(e)
        dt = dtype or jnp.float32
        return cls(e.n, e.n_pad, e.n_parts, e.vec_size, g["has_er"],
                   t["ell_vals"], t["ell_cols"], t["er_vals"], t["er_cols"],
                   t["er_row_idx"],
                   jnp.asarray(g["er_p_vals"], dtype=dt),
                   jnp.asarray(g["er_p_cols"]),
                   jnp.asarray(g["er_p_rows"]),
                   t["perm"], t["inv_perm"])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EHYBPackedDevice:
    """Device-side packed-staircase EHYB: the Pallas kernel's tables
    (see :class:`repro.core.ehyb.PackedEHYB` for the tile layout)."""

    n: int
    n_pad: int
    n_parts: int
    vec_size: int
    has_er: bool
    packed_vals: jnp.ndarray    # (P, T, Sb, 128)
    packed_cols: jnp.ndarray    # (P, T, Sb, 128) uint16
    col_starts: jnp.ndarray     # (P, W+1) int32 first tile of column k
    col_rows: jnp.ndarray       # (P, W) int32 active rows of column k
    er_vals: jnp.ndarray
    er_cols: jnp.ndarray
    er_row_idx: jnp.ndarray
    er_p_vals: jnp.ndarray      # (P, E, We) fused-ER tiles (see EHYBDevice)
    er_p_cols: jnp.ndarray
    er_p_rows: jnp.ndarray
    perm: jnp.ndarray
    inv_perm: jnp.ndarray
    # tuned kernel parameters (repro.tuning.TunedParams.token(): sorted
    # (name, value) pairs, or () for library defaults).  Static aux, not a
    # leaf: the kernel wrappers read it at trace time, so two operators
    # tuned differently have different treedefs and can never share a jit
    # cache entry — while refill-style rebinds (same tuning, new values)
    # keep the treedef and stay retrace-free.
    kparams: tuple = ()

    def tree_flatten(self):
        leaves = (self.packed_vals, self.packed_cols, self.col_starts,
                  self.col_rows, self.er_vals, self.er_cols, self.er_row_idx,
                  self.er_p_vals, self.er_p_cols, self.er_p_rows,
                  self.perm, self.inv_perm)
        return leaves, (self.n, self.n_pad, self.n_parts, self.vec_size,
                        self.has_er, self.kparams)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        *head, kparams = aux
        return cls(*head, *leaves, kparams=kparams)

    @classmethod
    def from_packed(cls, pk, dtype=jnp.float32, kparams: tuple = ()):
        e = pk.base
        t = e.as_jax(dtype=dtype)
        g = group_er_by_partition(e)
        return cls(e.n, e.n_pad, e.n_parts, e.vec_size, g["has_er"],
                   jnp.asarray(pk.packed_vals, dtype=dtype),
                   jnp.asarray(pk.packed_cols),
                   jnp.asarray(pk.col_starts), jnp.asarray(pk.col_rows),
                   t["er_vals"], t["er_cols"], t["er_row_idx"],
                   jnp.asarray(g["er_p_vals"], dtype=dtype),
                   jnp.asarray(g["er_p_cols"]),
                   jnp.asarray(g["er_p_rows"]),
                   t["perm"], t["inv_perm"], kparams=kparams)


# ---------------------------------------------------------------------------
# SpMV / SpMM
# ---------------------------------------------------------------------------

def _as_2d(x: jnp.ndarray) -> tuple[jnp.ndarray, bool]:
    if x.ndim == 1:
        return x[:, None], True
    return x, False


@partial(jax.jit, static_argnames=())
def coo_spmv(m: COODevice, x: jnp.ndarray) -> jnp.ndarray:
    x2, squeeze = _as_2d(x)
    contrib = m.vals[:, None] * x2[m.cols]
    y = jax.ops.segment_sum(contrib, m.rows, num_segments=m.n)
    return y[:, 0] if squeeze else y


# CSR in XLA-land: row-pointer semantics realized as a segment-sum over a
# precomputed row stream (identical traffic to GPU scalar/vector CSR).
csr_spmv = coo_spmv


@jax.jit
def ell_spmv(m: ELLDevice, x: jnp.ndarray) -> jnp.ndarray:
    x2, squeeze = _as_2d(x)
    g = x2[m.cols]                       # (n, W, R)
    y = jnp.einsum("nw,nwr->nr", m.vals, g)
    return y[:, 0] if squeeze else y


@jax.jit
def hyb_spmv(m: HYBDevice, x: jnp.ndarray) -> jnp.ndarray:
    x2, squeeze = _as_2d(x)
    y = jnp.einsum("nw,nwr->nr", m.ell_vals, x2[m.ell_cols])
    spill = m.coo_vals[:, None] * x2[m.coo_cols]
    y = y + jax.ops.segment_sum(spill, m.coo_rows, num_segments=m.n)
    return y[:, 0] if squeeze else y


def _ehyb_ell_part(ell_vals, ell_cols, x_parts):
    """Cached part: per-partition gather from the partition's own x-slice.

    This is the operation the Pallas kernel implements with an explicit VMEM
    block; here it is expressed as a vmapped local gather so XLA sees the
    locality too (all gathers index a (V,)-sized operand, not the full x)."""
    def one_part(xv, cols, vals):     # xv: (V, R), cols: (V, W), vals: (V, W)
        g = xv[cols.astype(jnp.int32)]           # (V, W, R)
        return jnp.einsum("vw,vwr->vr", vals, g)

    return jax.vmap(one_part)(x_parts, ell_cols, ell_vals)   # (P, V, R)


def _to_permuted(obj, x: jnp.ndarray) -> tuple[jnp.ndarray, bool]:
    """Original (n[,R]) vector(s) -> permuted padded (n_pad[,R]) space."""
    x2, squeeze = _as_2d(x)
    xpad = jnp.concatenate(
        [x2, jnp.zeros((obj.n_pad - obj.n, x2.shape[1]), dtype=x2.dtype)],
        axis=0)
    return xpad[obj.perm], squeeze


def _from_permuted(obj, y_new: jnp.ndarray, squeeze: bool) -> jnp.ndarray:
    y = y_new[obj.inv_perm[: obj.n]]
    return y[:, 0] if squeeze else y


def _fused_er_parts(x_new, er_p_vals, er_p_cols, er_p_rows, vec_size):
    """Per-partition ER contribution in (P, V, R) layout: each partition
    gathers its own ER rows from the full x and scatters them LOCALLY into
    its (V, R) output block.  No global scatter-add.  The XLA formats and
    the Pallas format (whose kernel covers only the cached part) share it."""
    R = x_new.shape[1]

    def one_part(vals, cols, rows):
        g = x_new[cols]                                  # (E, We, R)
        ye = jnp.einsum("ew,ewr->er", vals, g)           # (E, R)
        return jnp.zeros((vec_size, R), dtype=ye.dtype).at[rows].add(ye)

    return jax.vmap(one_part)(er_p_vals, er_p_cols, er_p_rows)


@jax.jit
def ehyb_spmv_permuted(m: EHYBDevice, x_new: jnp.ndarray) -> jnp.ndarray:
    """EHYB SpMV/SpMM in the permuted space: x_new, y_new are (n_pad[, R]).

    The hot-loop form: no pad, no ``perm``/``inv_perm`` gathers, ER fused
    into the per-partition accumulation."""
    x2, squeeze = _as_2d(x_new)
    R = x2.shape[1]
    x_parts = x2.reshape(m.n_parts, m.vec_size, R)
    y_parts = _ehyb_ell_part(m.ell_vals, m.ell_cols, x_parts)
    if m.has_er:
        y_parts = y_parts + _fused_er_parts(
            x2, m.er_p_vals, m.er_p_cols, m.er_p_rows, m.vec_size).astype(
                y_parts.dtype)
    y_new = y_parts.reshape(m.n_pad, R)
    return y_new[:, 0] if squeeze else y_new


@jax.jit
def ehyb_spmv(m: EHYBDevice, x: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp EHYB SpMV/SpMM in the ORIGINAL space (oracle for the Pallas
    kernel): one permuted-space apply bracketed by the per-call perm /
    inv_perm gathers that :func:`ehyb_spmv_permuted` lets solvers hoist."""
    x_new, squeeze = _to_permuted(m, x)
    y_new = ehyb_spmv_permuted(m, x_new)
    return _from_permuted(m, y_new, squeeze)


def ehyb_spmv_buckets(b: EHYBBuckets, x: jnp.ndarray,
                      dtype=jnp.float32) -> jnp.ndarray:
    """Width-bucketed EHYB from the HOST container (uploads per call; kept as
    the transparent reference — hot paths use :class:`EHYBBucketsDevice`)."""
    e = b.base
    x2, squeeze = _as_2d(x)
    R = x2.shape[1]
    xpad = jnp.concatenate(
        [x2, jnp.zeros((e.n_pad - e.n, R), dtype=x2.dtype)], axis=0)
    x_new = xpad[jnp.asarray(e.perm)]
    x_parts = x_new.reshape(e.n_parts, e.vec_size, R)
    y_parts = jnp.zeros((e.n_parts, e.vec_size, R), dtype=x2.dtype)
    for pid, vals, cols in zip(b.part_ids, b.vals, b.cols):
        xv = x_parts[jnp.asarray(pid)]
        yv = _ehyb_ell_part(jnp.asarray(vals, dtype=dtype), jnp.asarray(cols), xv)
        y_parts = y_parts.at[jnp.asarray(pid)].set(yv)
    y_new = y_parts.reshape(e.n_pad, R)
    g = x_new[jnp.asarray(e.er_cols)]
    y_er = jnp.einsum("ew,ewr->er", jnp.asarray(e.er_vals, dtype=dtype), g)
    y_new = y_new.at[jnp.asarray(e.er_row_idx)].add(y_er)
    y = y_new[jnp.asarray(e.inv_perm[: e.n])]
    return y[:, 0] if squeeze else y


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EHYBBucketsDevice:
    """Device-side width-bucketed EHYB: all tables uploaded once, pytree-
    registered so the bucketed SpMV jits like every other device format
    (the host :class:`EHYBBuckets` path re-uploaded per call).  Per-bucket
    widths are static aux; the host container rides along outside the pytree
    for the distributed path to recover the partition structure."""

    n: int
    n_pad: int
    n_parts: int
    vec_size: int
    has_er: bool
    widths: tuple            # static per-bucket tile widths
    part_ids: tuple          # tuple[jnp.ndarray (B_i,)]
    vals: tuple              # tuple[jnp.ndarray (B_i, V, W_i)]
    cols: tuple              # tuple[jnp.ndarray (B_i, V, W_i)]
    er_p_vals: jnp.ndarray   # fused-ER tiles (see EHYBDevice)
    er_p_cols: jnp.ndarray
    er_p_rows: jnp.ndarray
    perm: jnp.ndarray
    inv_perm: jnp.ndarray
    # Host EHYBBuckets handle (dist path recovers partition structure from
    # it).  Deliberately NOT part of the pytree aux: value refills swap in a
    # refreshed host object, and keying jit caches on its identity would
    # recompile every permuted/bucketed apply per refill.  Unflattened copies
    # (inside traced code) carry None.
    host: object = None

    def tree_flatten(self):
        nb = len(self.part_ids)
        leaves = (*self.part_ids, *self.vals, *self.cols, self.er_p_vals,
                  self.er_p_cols, self.er_p_rows, self.perm, self.inv_perm)
        aux = (self.n, self.n_pad, self.n_parts, self.vec_size, self.has_er,
               self.widths, nb)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        *head, nb = aux
        part_ids = tuple(leaves[:nb])
        vals = tuple(leaves[nb:2 * nb])
        cols = tuple(leaves[2 * nb:3 * nb])
        rest = leaves[3 * nb:]
        return cls(*head, part_ids, vals, cols, *rest, host=None)

    @classmethod
    def from_buckets(cls, b: EHYBBuckets, dtype=jnp.float32):
        e = b.base
        g = group_er_by_partition(e)
        return cls(e.n, e.n_pad, e.n_parts, e.vec_size, g["has_er"],
                   tuple(b.widths),
                   tuple(jnp.asarray(p) for p in b.part_ids),
                   tuple(jnp.asarray(v, dtype=dtype) for v in b.vals),
                   tuple(jnp.asarray(c) for c in b.cols),
                   jnp.asarray(g["er_p_vals"], dtype=dtype),
                   jnp.asarray(g["er_p_cols"]),
                   jnp.asarray(g["er_p_rows"]),
                   jnp.asarray(e.perm), jnp.asarray(e.inv_perm),
                   host=b)


@jax.jit
def ehyb_buckets_spmv_permuted(m: EHYBBucketsDevice,
                               x_new: jnp.ndarray) -> jnp.ndarray:
    """Bucketed EHYB SpMV/SpMM in the permuted space (device container)."""
    x2, squeeze = _as_2d(x_new)
    R = x2.shape[1]
    x_parts = x2.reshape(m.n_parts, m.vec_size, R)
    y_parts = jnp.zeros((m.n_parts, m.vec_size, R), dtype=x2.dtype)
    for pid, vals, cols in zip(m.part_ids, m.vals, m.cols):
        yv = _ehyb_ell_part(vals, cols, x_parts[pid])
        y_parts = y_parts.at[pid].set(yv.astype(x2.dtype))
    if m.has_er:
        y_parts = y_parts + _fused_er_parts(
            x2, m.er_p_vals, m.er_p_cols, m.er_p_rows, m.vec_size).astype(
                y_parts.dtype)
    y_new = y_parts.reshape(m.n_pad, R)
    return y_new[:, 0] if squeeze else y_new


@jax.jit
def ehyb_buckets_spmv(m: EHYBBucketsDevice, x: jnp.ndarray) -> jnp.ndarray:
    """Bucketed EHYB SpMV/SpMM, original space (device container)."""
    x_new, squeeze = _to_permuted(m, x)
    y_new = ehyb_buckets_spmv_permuted(m, x_new)
    return _from_permuted(m, y_new, squeeze)


def dense_spmv(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    return a @ x


# ---------------------------------------------------------------------------
# unified entry point: spmv(A, x) / build_spmv(A)
# ---------------------------------------------------------------------------
# One API over every registered format.  Format selection, the cost model and
# the measured pass live in ``repro.autotune`` (imported lazily so host-side
# preprocessing stays importable without pulling the registry in).  Every
# consumer — solvers, the sparse linear layer, serving, benchmarks, the
# examples — routes through here; later PRs (sharding, batching,
# multi-backend) plug new formats into the registry and inherit the callers.

@dataclasses.dataclass
class SpMVOperator:
    """A sparse matrix bound to its selected device format.

    ``op(x)`` runs the SpMV/SpMM; ``op.format`` names the chosen format;
    ``op.tuning`` (when selected by the autotuner) holds the full
    :class:`repro.autotune.TuneResult` with the per-format modeled bytes.

    **Operator lifecycle.**  The expensive part of an operator is its
    *structure* (partitioning, reordering, packing, the jitted applies'
    XLA compilations) — all functions of the sparsity pattern alone.  When
    only the entry values change (transient/nonlinear FEM re-assembly,
    pruned-layer optimizer steps), ``op.update_values(a_new)`` returns an
    operator with freshly filled value tables and *everything else shared*:
    same structural device arrays, same pytree structure, same ``apply``
    closures — so it triggers zero partitioning work and zero XLA
    recompilation.  ``spmv()``/``solve()`` apply this transparently through
    the two-level operator cache (pattern hash → structure, matrix key →
    values).

    **Execution spaces.** EHYB-family formats compute in a symmetrically
    reordered, padded vector space.  ``op(x)`` takes and returns
    original-space vectors, paying a ``perm`` gather on the way in and an
    ``inv_perm`` gather on the way out *per call*.  When
    ``op.supports_permuted``, hot loops should instead hoist the permutation:
    ``x_new = op.to_permuted(x)`` once, ``op.matvec_permuted`` per iteration
    (operating on (n_pad[, R]) permuted vectors), ``op.from_permuted(y_new)``
    once at the end — the contract ``core.solver.solve`` runs on.
    """

    format: str
    obj: object                       # device container of ``format``
    apply: callable                   # (obj, x) -> y, original space
    n: int
    nnz: int
    tuning: object = None             # TuneResult | None
    apply_permuted: callable = None   # (obj, x_new) -> y_new, or None
    dtype: object = None              # value dtype of the device tables
    pattern_key: str = None           # sparsity-pattern hash (refill guard)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.apply(self.obj, x)

    def update_values(self, a_new, *, pattern: str = None) -> "SpMVOperator":
        """Same sparsity pattern, new values: refresh the value tables only.

        Returns a new operator whose device container shares every
        structural array with this one (columns, permutations, packing
        metadata) and keeps the same jitted ``apply`` closures, so repeated
        value updates neither re-partition nor recompile.  Formats without a
        registry ``refill`` hook fall back to a full build.

        ``pattern`` (a precomputed ``pattern_hash(a_new)``) skips re-hashing
        the index arrays for the pattern-identity guard — the operator cache
        already holds it.
        """
        from .. import autotune as at

        if a_new.n != self.n or a_new.nnz != self.nnz or (
                self.pattern_key is not None
                and (pattern or at.pattern_hash(a_new)) != self.pattern_key):
            raise ValueError(
                "update_values needs a matrix with the identical sparsity "
                "pattern; build a fresh operator for a new pattern")
        dtype = self.dtype or jnp.float32
        spec = at.get_format(self.format)
        if spec.refill is None:
            return _build_operator(a_new, self.format, dtype)
        obj = spec.refill(self.obj, a_new, dtype, {})
        return dataclasses.replace(self, obj=obj)

    @property
    def matvec(self):
        """The bare ``x -> y`` closure (what the Krylov solvers take)."""
        return self.__call__

    # ---- permuted-space execution -----------------------------------------

    @property
    def supports_permuted(self) -> bool:
        return self.apply_permuted is not None

    @property
    def n_pad(self) -> int:
        """Padded dimension of the permuted space."""
        return self.obj.n_pad if self.supports_permuted else self.n

    def to_permuted(self, x: jnp.ndarray) -> jnp.ndarray:
        """Original (n[, R]) -> permuted padded (n_pad[, R]).  Once per solve."""
        if not self.supports_permuted:
            raise ValueError(f"format {self.format!r} has no permuted space")
        xn, squeeze = _to_permuted(self.obj, jnp.asarray(x))
        return xn[:, 0] if squeeze else xn

    def from_permuted(self, y_new: jnp.ndarray) -> jnp.ndarray:
        """Permuted padded (n_pad[, R]) -> original (n[, R]).  Once per solve."""
        if not self.supports_permuted:
            raise ValueError(f"format {self.format!r} has no permuted space")
        y2, squeeze = _as_2d(jnp.asarray(y_new))
        return _from_permuted(self.obj, y2, squeeze)

    def _permuted_call(self, x_new: jnp.ndarray) -> jnp.ndarray:
        return self.apply_permuted(self.obj, x_new)

    @property
    def matvec_permuted(self):
        """``x_new -> y_new`` in the permuted space (bound method, so its
        hash is stable and jitted solver loops don't recompile per access)."""
        if not self.supports_permuted:
            raise ValueError(f"format {self.format!r} has no permuted space")
        return self._permuted_call


def _build_operator(a, format: str = "auto", dtype=None, *,
                    mode: str = "model", candidates=None, shared: dict = None,
                    context: str = "spmv", n_dev: int = 1,
                    k: int = 1) -> SpMVOperator:
    """Build the SpMV engine operator for CSR matrix ``a`` (the internal,
    non-deprecated form of the old ``build_spmv``; ``repro.api.Plan`` binds
    through this).

    format="auto"    — pick via the autotuner (cost model; ``mode="measure"``
                       additionally times the top candidates on-device);
    format=<name>    — force a registered format ("csr", "ell", "hyb",
                       "ehyb", "ehyb_bucketed", "ehyb_packed", "dense").
    context          — workload the byte model ranks for: "spmv" (one-shot
                       call, original space, permutation paid per call),
                       "solver" (iterative hot loop in the permuted space,
                       permutation hoisted and amortized), or "dist" (a
                       hot-loop iteration sharded over ``n_dev`` devices,
                       interconnect term included).
    k                — expected rhs batch width (SpMM); steers the ranking
                       only, applies accept any width at run time.
    """
    from .. import autotune as at

    dtype = dtype or jnp.float32
    shared = {} if shared is None else shared   # carries the host EHYB build
    tuning = None
    if format == "auto":
        tuning = at.autotune(a, dtype, mode=mode, candidates=candidates,
                             shared=shared, context=context, n_dev=n_dev,
                             k=k)
        format = tuning.format
    spec = at.get_format(format)
    obj, apply = spec.build(a, dtype, shared)
    return SpMVOperator(format=format, obj=obj, apply=apply, n=a.n,
                        nnz=a.nnz, tuning=tuning,
                        apply_permuted=spec.permuted, dtype=dtype,
                        pattern_key=tuning.key if tuning
                        else at.pattern_hash(a))


def build_spmv(a, format: str = "auto", dtype=None, *, mode: str = "model",
               candidates=None, shared: dict = None,
               context: str = "spmv", n_dev: int = 1) -> SpMVOperator:
    """Deprecated: use ``repro.api.plan(a).bind(a)`` (Operator API v2).

    Kept as a thin shim over the same engine; behavior is unchanged.
    """
    import warnings

    warnings.warn(
        "core.spmv.build_spmv is deprecated; use repro.api.plan(a"
        ", execution=ExecutionConfig(...)).bind(a) — see README 'API v2'",
        DeprecationWarning, stacklevel=2)
    return _build_operator(a, format, dtype, mode=mode,
                           candidates=candidates, shared=shared,
                           context=context, n_dev=n_dev)


def cached_spmv_operator(a, format: str = "auto", dtype=None,
                         context: str = "spmv") -> SpMVOperator:
    """The engine operator for ``a``, memoized through the Operator API v2
    :class:`repro.api.PlanCache` (which replaced the module-level
    ``_OP_CACHE``/``_OP_PATTERN_CACHE`` globals that used to live here):

    1. value-inclusive matrix hash — an exact hit returns the *same*
       operator object, keeping its matvec jit-cache-stable (repeated
       calls neither rebuild device arrays nor retrigger XLA compilation);
    2. sparsity-pattern hash — same pattern, new values refreshes the plan's
       bound operator through ``update_values``: one value scatter + upload,
       zero partitioning/reordering/packing and zero recompilation.  This is
       what makes per-step value updates (transient FEM, ``SparseLinear``
       training, served pruned heads) amortize preprocessing across the
       pattern's lifetime instead of paying it per update.
    """
    from ..api import ExecutionConfig
    from ..api.plan import plan as _plan

    dtype = dtype or jnp.float32
    p = _plan(a, execution=ExecutionConfig(format=format, workload=context))
    return p._template_for(dtype, a)


def spmv(a, x: jnp.ndarray, format: str = "auto", dtype=None) -> jnp.ndarray:
    """Deprecated: use ``repro.api`` (``plan(A).bind(A) @ x``).

    Unified SpMV: ``y = A @ x`` for a SparseCSR ``A`` in the best format.
    The built operator is cached per sparsity pattern in the visible
    ``repro.api.PLAN_CACHE``, so repeated calls on the same pattern pay one
    build — and calls with the same pattern but *new values* pay one value
    refill.  ``x`` may be (n,) or (n, R); dtype defaults to ``x.dtype`` for
    floating/complex ``x`` and float32 otherwise (an integer rhs must not
    build integer value tables).
    """
    import warnings

    warnings.warn(
        "core.spmv.spmv is deprecated; use repro.api: plan(A).bind(A) @ x",
        DeprecationWarning, stacklevel=2)
    if isinstance(a, SpMVOperator):
        return a(x)
    if not isinstance(a, SparseCSR):
        from ..api.operator import LinearOperator
        from ..dist.operator import ShardedOperator

        if isinstance(a, (ShardedOperator, LinearOperator)):
            return a(x)         # promotes non-float x itself
    x = jnp.asarray(x)
    if dtype is None:
        dtype = (x.dtype if jnp.issubdtype(x.dtype, jnp.inexact)
                 else jnp.float32)
    if not jnp.issubdtype(x.dtype, jnp.inexact):
        x = x.astype(dtype)
    return cached_spmv_operator(a, format, dtype)(x)
