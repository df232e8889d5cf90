"""EHYB format construction (paper §3.2–3.4, Algorithms 1–2).

The Explicit-caching HYBrid format splits a partitioned, symmetrically
reordered sparse matrix into:

* a **sliced-ELL part** holding every entry whose column lies in the same
  partition as its row.  Column indices are stored *locally* (offset within
  the partition's x-slice) as ``uint16`` — the paper's §3.4 compact-index
  optimization (25 % fewer bytes/nnz in fp32, 13.3 % in fp64).  Rows are
  sorted by in-partition length inside each partition (Algo 1 line 17–18),
  which tightens slices/tiles.
* an **ER ("extra rows") part** holding the out-of-partition remainder in a
  row-length-sorted padded layout with global column indices and an explicit
  row map ``er_row_idx`` (the paper's ``yIdxER``).

TPU adaptation (see DESIGN.md §2): the GPU's (partition ↔ CUDA block,
x-slice ↔ shared memory, 32-row warp slice) becomes (partition ↔ Pallas grid
step, x-slice ↔ VMEM block via BlockSpec, 8-row sublane slice).  Tiles are
uniform ``(vec_size, ell_width)`` across partitions in the baseline format so
one ``BlockSpec`` covers the whole kernel; the width-bucketed variant
(§build_buckets) is the beyond-paper optimization that recovers most of the
padding bytes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .counters import bump, span
from .matrices import SparseCSR
from .partition import LANES, Partition, lane_geometry, make_partition


@dataclasses.dataclass
class EHYB:
    """EHYB matrix. All arrays are host numpy; see ``as_jax`` for device form."""

    n: int                   # true dimension
    n_pad: int               # n_parts * vec_size
    n_parts: int
    vec_size: int
    # --- sliced-ELL (cached) part: uniform tiles -------------------------
    ell_width: int                    # W = max in-partition row width
    ell_vals: np.ndarray              # (n_parts, vec_size, W) float
    ell_cols: np.ndarray              # (n_parts, vec_size, W) uint16, LOCAL
    part_widths: np.ndarray           # (n_parts,) int32 — per-partition max width
    slice_widths: np.ndarray          # (n_parts, vec_size//sublane) int32 —
    # per 8-row-slice max width (the paper's sliced-ELL granularity; rows are
    # length-sorted inside each partition so slices are tight)
    # --- ER (uncached) part ----------------------------------------------
    er_rows: int                      # padded to sublane multiple (≥ 1 slice)
    er_width: int
    er_vals: np.ndarray               # (er_rows, er_width) float
    er_cols: np.ndarray               # (er_rows, er_width) int32, GLOBAL (new order)
    er_row_idx: np.ndarray            # (er_rows,) int32 — new-row of each ER slot
    # --- permutations ------------------------------------------------------
    perm: np.ndarray                  # (n_pad,) new slot -> old vertex (>=n: padding)
    inv_perm: np.ndarray              # (n_pad,) old (padded) vertex -> new slot
    # --- provenance / stats -------------------------------------------------
    nnz: int
    nnz_in: int                       # in-partition entries
    preprocess_seconds: dict = dataclasses.field(default_factory=dict)
    # --- value-refresh scatter plan (see ``refill``) ----------------------
    # ``ell_dst``/``er_dst``: flat destination indices into the (padded) ELL
    # and ER value tables; ``ell_src``/``er_src``: matching indices into the
    # CSR ``data`` stream; ``ell_widths``: (n_pad,) pattern row widths;
    # ``n_er_live``: live (pattern-bearing) ER slots.  Pattern-only — a new
    # value buffer on the same pattern replays the scatter with no
    # partitioning, reordering or sorting.
    fill_plan: Optional[dict] = None
    # registry name of the partition strategy that produced ``perm``
    # (provenance; carried through ``refill`` via dataclasses.replace)
    partition_method: str = "bfs"

    # .....................................................................
    @property
    def in_part_fraction(self) -> float:
        return self.nnz_in / max(self.nnz, 1)

    @property
    def ell_padding_ratio(self) -> float:
        stored = self.n_parts * self.vec_size * self.ell_width
        return stored / max(self.nnz_in, 1)

    def bytes_moved(self, val_bytes: int = 4, col_bytes: int = 2,
                    layout: str = "sliced", space: str = "permuted",
                    fused_er: bool = True, halo_words: Optional[int] = None,
                    n_dev: int = 1, k: int = 1) -> dict:
        """Modeled HBM traffic of one SpMV (the paper's §3.4 accounting).

        ELL streams vals + uint16 local cols once; every partition streams its
        x-slice into VMEM once (that is the explicit cache); ER streams vals +
        int32 cols + one random x-read per entry; y written once.

        layout: "sliced"  — the paper's sliced-ELL (per 8-row-slice widths;
                            padding only inside a slice),
                "tile"    — uniform (V, W) partition tiles (the XLA path),
                "packed"  — the Pallas kernel's staircase of aligned
                            (Sb, 128) tiles, padded to the longest
                            partition stream (what ``PackedEHYB`` stores).

        space: which vector space the caller hands x/y over in.
               "permuted" — the kernel-proper traffic (x and y already live in
               the EHYB-reordered space; this is what the paper's accounting
               measures and what the permuted-space solver loop pays per
               iteration);
               "original" — adds the per-call permutation round trip
               (``perm`` gather on x plus ``inv_perm`` gather on y:
               2·n_pad·val_bytes), the overhead a single original-space
               ``spmv()`` call cannot avoid.

        fused_er: ER contribution computed inside the main kernel (each
               partition owns its ER rows; x is VMEM-resident once for all of
               them) — the default, matching the shipped execution paths —
               vs a second launch with one random x-read per ER entry plus a
               caller-side scatter-add (2·er_rows·val_bytes of y
               read-modify-write), kept for the ablation.

        halo_words / n_dev: the interconnect term for mesh-sharded
               execution (``context="dist"``): ``halo_words`` is the
               scheduled per-iteration exchange payload of the
               :class:`repro.dist.HaloPlan` (per rhs column), added as
               ``interconnect = halo_words · val_bytes`` when ``n_dev > 1``.
               Interconnect bytes are far more expensive per byte than HBM
               bytes, but SpMV moves so few of them after the halo
               compaction that a single combined total still ranks formats
               correctly — the per-channel breakdown stays in the dict for
               callers that weight them separately.

        k: rhs batch width of a multi-rhs (SpMM) apply.  The A streams
               (ELL vals/cols, ER vals/cols/rows) are read ONCE regardless
               of k — that is the whole point of the explicit cache — while
               every x/y-sided term (x_cache, the ER x-gather, y, the
               permutation round trip, the halo payload) scales ×k.
               Arithmetic intensity therefore grows with k and the SpMM
               crossover between formats moves; ``autotune(..., k=)`` ranks
               with this axis.
        """
        if layout == "tile" or self.slice_widths is None:
            ell_n = self.n_parts * self.vec_size * self.ell_width
        elif layout == "sliced":
            ell_n = int(self.slice_widths.sum()) * 8
        else:  # packed
            _, sb = lane_geometry(self.vec_size)
            tile = sb * LANES
            tiles = -(-staircase_rows(self) // tile)
            ell_n = int(tiles.sum(axis=1).max(initial=1)) * tile \
                * self.n_parts
        ell = ell_n * (val_bytes + col_bytes)
        x_cache = self.n_pad * val_bytes * k
        er_n = self.er_rows * self.er_width
        has_er = bool(self.er_vals.any())
        if fused_er:
            # vals + cols stream once — at the PADDED per-partition tile
            # size (P, E, We) the fused kernel actually reads, not the flat
            # table (consistent with the ELL term, which also counts its
            # padding); the ER x-gather hits the resident VMEM copy of x
            # (streamed in once, bounded by n_pad); the scatter-add
            # disappears (each grid step accumulates its own ER rows into
            # its (V, R) output block).
            if has_er:
                g = group_er_by_partition(self)
                er_x = min(er_n, self.n_pad) * val_bytes * k
                er = (g["er_p_vals"].size * (val_bytes + 4) + er_x
                      + g["er_p_rows"].size * 4)
            else:
                er = 0      # ER stage skipped statically
        else:
            er = (er_n * (val_bytes + 4) + er_n * val_bytes * k
                  + self.er_rows * 4
                  + (2 * self.er_rows * val_bytes * k if has_er else 0))
        y = self.n_pad * val_bytes * k
        perm = 2 * self.n_pad * val_bytes * k if space == "original" else 0
        ic = (halo_words or 0) * val_bytes * k if n_dev > 1 else 0
        return {"ell": ell, "x_cache": x_cache, "er": er, "y": y,
                "perm": perm, "interconnect": ic,
                "total": ell + x_cache + er + y + perm + ic}

    def as_jax(self, dtype=None):
        """Return a dict of jnp arrays (lazy import keeps preprocessing
        importable without jax)."""
        import jax.numpy as jnp

        dt = dtype or jnp.float32
        return {
            "ell_vals": jnp.asarray(self.ell_vals, dtype=dt),
            "ell_cols": jnp.asarray(self.ell_cols),            # uint16
            "er_vals": jnp.asarray(self.er_vals, dtype=dt),
            "er_cols": jnp.asarray(self.er_cols),
            "er_row_idx": jnp.asarray(self.er_row_idx),
            "perm": jnp.asarray(self.perm),
            "inv_perm": jnp.asarray(self.inv_perm),
        }

    def refill(self, new_data: np.ndarray) -> "EHYB":
        """Same sparsity pattern, new values: replay the build-time scatter.

        Returns a new :class:`EHYB` sharing every structural array (columns,
        permutations, widths, the plan itself) with ``self``; only the value
        tables are rewritten — one vectorized numpy scatter, no partitioning,
        no reordering, no sorting.  Memoized derived views that ``self``
        already carries (``group_er_by_partition`` tiles, width buckets, the
        packed staircase, the ER window) are refilled through their own
        recorded plans, so downstream device builders touch no structure
        either.

        ``new_data`` must be the CSR ``data`` stream of a matrix with the
        *identical* pattern (same ``indptr``/``indices``) — callers above
        this layer key on ``pattern_hash`` to guarantee that.
        """
        if self.fill_plan is None:
            raise ValueError("this EHYB carries no fill plan (built before "
                             "value-refresh support); rebuild instead")
        new_data = np.asarray(new_data)
        if new_data.shape != (self.nnz,):
            raise ValueError(f"value buffer has {new_data.shape} entries; "
                             f"pattern holds {self.nnz}")
        bump("ehyb_refill")
        with span("repro.ehyb.refill") as refill:
            plan = self.fill_plan
            ell = np.zeros(self.n_pad * self.ell_width, dtype=np.float64)
            ell[plan["ell_dst"]] = new_data[plan["ell_src"]]
            ell = ell.reshape(self.n_parts, self.vec_size, self.ell_width)
            er = np.zeros(self.er_rows * self.er_width, dtype=np.float64)
            er[plan["er_dst"]] = new_data[plan["er_src"]]
            er = er.reshape(self.er_rows, self.er_width)
            new = dataclasses.replace(self, ell_vals=ell, er_vals=er,
                                      preprocess_seconds={})
            g = getattr(self, "_er_grouped", None)
            if g is not None:
                new._er_grouped = refill_grouped(g, er)
            def _refill_buckets(b):
                return EHYBBuckets(
                    base=new, part_ids=b.part_ids,
                    vals=[np.ascontiguousarray(ell[ch, :, : v.shape[2]])
                          for ch, v in zip(b.part_ids, b.vals)],
                    cols=b.cols, widths=b.widths)

            b = getattr(self, "_buckets", None)
            if b is not None:
                new._buckets = _refill_buckets(b)
            # non-default bucket counts (tuned n_buckets) memoize apart —
            # refill them through the same value-only path so a tuned bucketed
            # operator never silently re-buckets
            nb = getattr(self, "_buckets_nb", None)
            if nb is not None:
                new._buckets_nb = {count: _refill_buckets(bb)
                                   for count, bb in nb.items()}
            pk = getattr(self, "_packed", None)
            if pk is not None:
                new._packed = pk.refill(new)
            w = getattr(self, "_er_window", None)
            if w is not None:
                new._er_window = w.refill(er)
        # structure passes cost exactly zero on a refill — that IS the point
        new.preprocess_seconds = {"partition": 0.0, "metadata": 0.0,
                                  "reorder": 0.0, "refill": refill.seconds,
                                  "total": refill.seconds}
        return new


def build_ehyb(m: SparseCSR, part: Optional[Partition] = None,
               method: str = "bfs", dtype_bytes: int = 4,
               sublane: int = 8, max_width: Optional[int] = None,
               **part_kw) -> EHYB:
    """Algorithms 1–2 of the paper, vectorized with numpy.

    ``max_width`` (beyond-paper knob, default off) caps the sliced-ELL width
    and spills over-long in-partition rows to the ER part — a robustness valve
    for power-law matrices.
    """
    bump("build_ehyb")
    if part is None:
        part = make_partition(m, method=method, dtype_bytes=dtype_bytes,
                              **part_kw)
    # a prebuilt `part` (e.g. the autotuned winner) carries its own timing
    t_part = getattr(part, "seconds", 0.0)

    with span("repro.ehyb.metadata") as meta:
        n, n_parts, V = m.n, part.n_parts, part.vec_size
        n_pad = part.n_pad
        rows = np.repeat(np.arange(n, dtype=np.int64), m.row_lengths())
        cols = m.indices.astype(np.int64)
        vals = m.data
        same = part.part_vec[rows] == part.part_vec[cols]

        # ---- per-row in-partition counts drive the within-partition sort
        # (Algo 1 lines 3–18) ----------------------------------------------
        in_counts = np.bincount(rows[same], minlength=n)
        # current slots from the partition (grouped by partition, orig order)
        base_slot = part.inv_perm[:n]
        part_of = base_slot // V
        # sort within each partition by (-in_count, orig index): stable,
        # exact
        order = np.lexsort((np.arange(n), -in_counts, part_of))
        # `order` lists vertices partition-major; rebuild slots with row-sort
        slot_rank = np.empty(n, dtype=np.int64)
        counts_per_part = np.bincount(part_of, minlength=n_parts)
        starts = np.concatenate([[0], np.cumsum(counts_per_part)])
        slot_rank[order] = np.arange(n) - starts[part_of[order]]
        inv_perm = np.full(n_pad, -1, dtype=np.int64)
        inv_perm[:n] = part_of * V + slot_rank
        # padding vertices fill remaining slots of each partition
        all_slots = np.zeros(n_pad, dtype=bool)
        all_slots[inv_perm[:n]] = True
        free_slots = np.flatnonzero(~all_slots)
        inv_perm[n:] = free_slots
        perm = np.empty(n_pad, dtype=np.int64)
        perm[inv_perm] = np.arange(n_pad)

        new_r = inv_perm[rows]
        new_c = inv_perm[cols]

        # ---- split in-partition / ER, with optional width cap -------------
        in_mask = same.copy()
        if max_width is not None:
            # spill entries beyond max_width per row (keep smallest local cols)
            ord_in = np.lexsort((new_c, new_r))
            rr = new_r[ord_in][same[ord_in]]
            # rank of each in-part entry within its row
            idx_in = ord_in[same[ord_in]]
            row_change = np.concatenate([[True], rr[1:] != rr[:-1]])
            grp_start = np.maximum.accumulate(np.where(row_change,
                                                       np.arange(len(rr)), 0))
            rank = np.arange(len(rr)) - grp_start
            spill = idx_in[rank >= max_width]
            in_mask[spill] = False

    with span("repro.ehyb.reorder") as reorder:
        # ---- fill sliced-ELL (Algo 2, lines 4–8) --------------------------
        sel = np.flatnonzero(in_mask)
        order_in = sel[np.lexsort((new_c[sel], new_r[sel]))]
        r_in = new_r[order_in]
        widths = np.bincount(r_in, minlength=n_pad)
        W = int(widths.max()) if len(r_in) else 1
        W = max(W, 1)
        part_widths = widths.reshape(n_parts, V).max(axis=1).astype(np.int32)
        row_start = np.concatenate([[0], np.cumsum(widths)])
        k = np.arange(len(r_in)) - row_start[r_in]
        ell_vals = np.zeros((n_pad, W), dtype=np.float64)
        ell_cols = np.zeros((n_pad, W), dtype=np.uint16)
        ell_vals[r_in, k] = vals[order_in]
        local = (new_c[order_in] - (r_in // V) * V)
        if V > (1 << 16):
            raise ValueError("vec_size exceeds uint16 local index range")
        ell_cols[r_in, k] = local.astype(np.uint16)
        ell_vals = ell_vals.reshape(n_parts, V, W)
        ell_cols = ell_cols.reshape(n_parts, V, W)
        # per 8-row-slice widths (paper's sliced-ELL accounting granularity)
        slice_widths = widths.reshape(n_parts, V // sublane, sublane).max(
            axis=2).astype(np.int32) if V % sublane == 0 else None

        # ---- fill ER (Algo 2, lines 10–13; Algo 1 lines 16, 23–26) --------
        sel_er = np.flatnonzero(~in_mask)
        er_counts = np.bincount(new_r[sel_er], minlength=n_pad)
        er_rows_idx = np.flatnonzero(er_counts)
        # global sort by descending out-count (Algo 1 line 16)
        er_rows_idx = er_rows_idx[np.argsort(-er_counts[er_rows_idx],
                                             kind="stable")]
        n_er = len(er_rows_idx)
        n_er_pad = max(sublane, -(-max(n_er, 1) // sublane) * sublane)
        er_width = int(er_counts.max()) if n_er else 1
        er_vals = np.zeros((n_er_pad, er_width), dtype=np.float64)
        er_cols = np.zeros((n_er_pad, er_width), dtype=np.int32)
        er_row_idx = np.zeros(n_er_pad, dtype=np.int32)
        er_dst = np.empty(0, dtype=np.int64)
        er_src = np.empty(0, dtype=np.int64)
        if n_er:
            er_row_idx[:n_er] = er_rows_idx
            er_slot = np.full(n_pad, -1, dtype=np.int64)
            er_slot[er_rows_idx] = np.arange(n_er)
            order_er = sel_er[np.lexsort((new_c[sel_er], new_r[sel_er]))]
            r_er = new_r[order_er]
            rs = np.concatenate(
                [[0], np.cumsum(np.bincount(r_er, minlength=n_pad))])
            kk = np.arange(len(r_er)) - rs[r_er]
            er_vals[er_slot[r_er], kk] = vals[order_er]
            er_cols[er_slot[r_er], kk] = new_c[order_er].astype(np.int32)
            er_dst = er_slot[r_er] * er_width + kk
            er_src = order_er
    t_meta, t_reorder = meta.seconds, reorder.seconds

    # value-refresh plan: the two scatters above, recorded as flat indices
    # (``refill`` replays them on a new value buffer with zero structure work)
    fill_plan = {"ell_dst": r_in * W + k, "ell_src": order_in,
                 "er_dst": er_dst, "er_src": er_src,
                 "ell_widths": widths.astype(np.int32),
                 "n_er_live": n_er}

    return EHYB(n=n, n_pad=n_pad, n_parts=n_parts, vec_size=V,
                ell_width=W, ell_vals=ell_vals, ell_cols=ell_cols,
                part_widths=part_widths, slice_widths=slice_widths,
                er_rows=n_er_pad, er_width=er_width, er_vals=er_vals,
                er_cols=er_cols, er_row_idx=er_row_idx,
                perm=perm, inv_perm=inv_perm,
                nnz=m.nnz, nnz_in=int(in_mask.sum()),
                preprocess_seconds={"partition": t_part, "metadata": t_meta,
                                    "reorder": t_reorder,
                                    "total": t_part + t_meta + t_reorder},
                fill_plan=fill_plan,
                partition_method=getattr(part, "method", "") or method)


# ---------------------------------------------------------------------------
# ER-by-partition grouping (per-partition ER tiles)
# ---------------------------------------------------------------------------

def group_er_by_partition(e: EHYB, sublane: int = 8,
                          keep: Optional[np.ndarray] = None) -> dict:
    """Map every ER slot to its owning partition (``er_row_idx // vec_size``).

    Every EHYB apply accumulates partition ``p``'s ER rows into the same
    (V, R) output block as its sliced-ELL part — no caller-side global
    scatter-add (``core.spmv._fused_er_parts``).  Returns uniform
    (P, E, We) tiles (E = max ER rows owned by any partition,
    sublane-aligned; empty slots hold zero values and row 0, which
    contribute nothing):

      ``er_p_vals``  (P, E, We) float
      ``er_p_cols``  (P, E, We) int32 global-new column indices
      ``er_p_rows``  (P, E)     int32 LOCAL row index within the partition

    ``keep`` (a boolean mask over the flat ``(er_rows, er_width)`` tables)
    groups only those entries: the rows that hold one, every other entry of
    them zeroed.  That is the ER window's leftover (:func:`pack_er_window`),
    and it is not memoized.  Without it the result is memoized on ``e`` so
    the device builders and the bytes model share one grouping pass.
    """
    if keep is None:
        cached = getattr(e, "_er_grouped", None)
        if cached is not None and cached["sublane"] == sublane:
            return cached
        bump("group_er")
    p_, v_, we = e.n_parts, e.vec_size, e.er_width
    if keep is not None:
        keep = np.asarray(keep).reshape(-1, we)
        live = np.flatnonzero(keep.any(axis=1))
    elif e.fill_plan is not None:
        # pattern-derived live set: ER slots [0, n_er) hold the live rows by
        # construction (value-independent — explicit zeros stay live, so a
        # later ``refill`` can never change the grouping)
        live = np.arange(e.fill_plan["n_er_live"])
    else:
        live = np.flatnonzero((e.er_vals != 0).any(axis=1))
    owner = e.er_row_idx[live] // v_
    counts = np.bincount(owner, minlength=p_) if len(live) else \
        np.zeros(p_, dtype=np.int64)
    em = int(counts.max()) if len(live) else 0
    ep = max(sublane, -(-max(em, 1) // sublane) * sublane)
    er_p_vals = np.zeros((p_, ep, we), dtype=e.er_vals.dtype)
    er_p_cols = np.zeros((p_, ep, we), dtype=np.int32)
    er_p_rows = np.zeros((p_, ep), dtype=np.int32)
    own = np.empty(0, dtype=np.int64)
    slot = np.empty(0, dtype=np.int64)
    src = np.empty(0, dtype=np.int64)
    kept = None
    if len(live):
        order = np.argsort(owner, kind="stable")
        src = live[order]
        own = owner[order]
        starts = np.concatenate([[0], np.cumsum(counts)])
        slot = np.arange(len(src)) - starts[own]
        kept = None if keep is None else keep[src]
        er_p_vals[own, slot] = _kept(e.er_vals[src], kept)
        er_p_cols[own, slot] = _kept(e.er_cols[src], kept)
        er_p_rows[own, slot] = (e.er_row_idx[src] % v_).astype(np.int32)
    out = {"er_p_vals": er_p_vals, "er_p_cols": er_p_cols,
           "er_p_rows": er_p_rows, "has_er": bool(len(live)),
           "n_er_live": int(len(live)), "sublane": sublane,
           # refill plan: er_p_vals[own, slot] = er_vals_new[src], its
           # entries outside ``keep`` zeroed (``kept``: (rows, We) or None)
           "own": own, "slot": slot, "src": src, "kept": kept}
    if keep is None:
        e._er_grouped = out
    return out


def _kept(rows: np.ndarray, kept: Optional[np.ndarray]) -> np.ndarray:
    return rows if kept is None else np.where(kept, rows, 0)


def refill_grouped(g: dict, er_vals: np.ndarray) -> dict:
    """``g`` (:func:`group_er_by_partition`) with values from a refilled
    ``er_vals`` table, through its recorded plan."""
    gp = np.zeros_like(g["er_p_vals"])
    gp[g["own"], g["slot"]] = _kept(np.asarray(er_vals)[g["src"]],
                                    g["kept"])
    return {**g, "er_p_vals": gp}


# ---------------------------------------------------------------------------
# the ER window: each partition's ER served from a cached window of x
# ---------------------------------------------------------------------------

# Window-local ER columns are uint16 (slot·128 + lane, the paper's §3.4
# compact index), which caps a partition's window at 2^16 / 128 lane-rows.
ER_WINDOW_LANE_ROWS = (1 << 16) // LANES
# VMEM bytes of one partition's window tiles (f32 values + uint16 columns):
# the kernel holds a partition's tiles whole, double-buffered, so the ER
# columns of a partition past this many bytes of tiles go to the leftover.
ER_WINDOW_TILE_BYTES = 16 * 1024 * 1024
# What one gather pass of the kernel over one (8, 128) window tile costs,
# in elements of the XLA ER gather, on a TPU v5e at the benchmark cells'
# size: about 3.9 ns a pass (H = 56 passes over each window tile) against
# 6.9-7.6 ns an element of the padded (P, E, We) gather.
ER_PASS_COST = 0.55


@dataclasses.dataclass
class ERWindow:
    """The ER part of a partition, served from an explicitly cached window
    of x (the Pallas kernel's ER stage).

    A partition's *ER window* is a set of 128-lane rows of the flat,
    128-padded permuted x that its ER entries read: ``win_rows[p]``, H
    lane-rows (the most lane-rows any partition keeps, padded to a multiple
    of 8; unused slots repeat lane-row 0).  The apply gathers those rows
    once as whole rows, and the kernel gathers the entries from VMEM the way
    it gathers from the partition's own x-slice.

    Entries are stored in :class:`PackedEHYB`'s tile layout: column k of
    partition p holds the entries of column k of the ER tables, rows in the
    partition's own order, so the output lands in its (V, R) block with no
    scatter.  Column k is the tiles that cover rows [0, ``col_rows[p, k]``)
    (up to its last row with an entry in ER column k or past it), from
    tile ``col_starts[p, k]``.  Columns are window-local:
    ``slot·128 + (col & 127)``.

    Each partition keeps its lane-rows that hold the most entries.  How
    many is chosen from the pattern (:func:`pack_er_window`); the entries
    on the other lane-rows (``leftover``) stay on the XLA ER path, in
    :func:`group_er_by_partition`'s tables (``left``; None when nothing is
    left over), and leave zeros in the window's tiles.  ``plan`` marks the
    window's entries in the ER tables (``mask``) and ``left`` records its
    own scatter, so :meth:`refill` touches no structure.
    """

    cap: int
    lane_rows: int                    # H
    win_rows: np.ndarray              # (P, H) int32 lane-rows of flat x
    vals: np.ndarray                  # (P, T, Sb, 128) float
    cols: np.ndarray                  # (P, T, Sb, 128) uint16 window-local
    col_starts: np.ndarray            # (P, W+1) int32 first tile of col k
    col_rows: np.ndarray              # (P, W) int32 rows covering col k
    entries: int                      # ER entries served from the window
    leftover: int                     # ER entries left to the XLA path
    left: Optional[dict]              # group_er_by_partition(keep=...)
    plan: dict                        # see _window_tiles

    def _tiles(self, table: np.ndarray) -> np.ndarray:
        return _window_tiles(table, self.plan, self.col_starts,
                             self.vals.shape).reshape(self.vals.shape)

    def refill(self, er_vals: np.ndarray) -> "ERWindow":
        """The same window with values from a refilled ``er_vals`` table."""
        er = np.asarray(er_vals)
        left = (None if self.left is None
                else refill_grouped(self.left, er))
        return dataclasses.replace(self, vals=self._tiles(er), left=left)

    def entry_slots(self) -> tuple:
        """(src, dst): each window entry's flat index in the ER tables and
        in the flat window tiles."""
        mask = self.plan["mask"]
        ids = self._tiles(np.arange(1, mask.size + 1).reshape(mask.shape))
        dst = np.flatnonzero(ids)
        return ids.reshape(-1)[dst] - 1, dst

    def left_entries(self) -> np.ndarray:
        """Flat ER-table indices of the leftover entries."""
        if self.left is None:
            return np.empty(0, dtype=np.int64)
        g = self.left
        we = g["er_p_vals"].shape[2]
        return (g["src"][:, None] * we + np.arange(we))[g["kept"]]


def _window_tiles(table: np.ndarray, plan: dict, col_starts: np.ndarray,
                  shape: tuple) -> np.ndarray:
    """The window's tiles (``shape`` = (P, T, Sb, 128)) of an (er_rows,
    er_width) ER table: the entries ``plan["mask"]`` marks, each ER row
    ``plan["slots"]`` moved to its row ``plan["rows"]`` of the padded x,
    each partition's rows transposed into its columns' tiles; zeros
    elsewhere."""
    p_, n_tiles, sb, lanes = shape
    tile = sb * lanes
    width = col_starts.shape[1] - 1
    v_ = plan["n_pad"] // p_
    slots, rows = plan["slots"], plan["rows"]
    n_sub = -(-v_ // tile)                       # tiles a column can take
    by_col = np.zeros((p_, width, n_sub * tile), dtype=table.dtype)
    by_col[rows // v_, :, rows % v_] = np.where(plan["mask"][slots, :width],
                                                table[slots, :width], 0)
    used = np.arange(n_sub) < np.diff(col_starts, axis=1)[:, :, None]
    out = np.zeros((p_, n_tiles, tile), dtype=table.dtype)
    out[np.arange(n_tiles) < col_starts[:, -1:]] = by_col.reshape(
        p_, width * n_sub, tile)[used.reshape(p_, -1)]
    return out


def _column_rows(rows: np.ndarray, span: np.ndarray, n_pad: int, v_: int,
                 width: int) -> np.ndarray:
    """(P, width) ``col_rows``: for each partition and column k, 1 + its
    last local row whose entries reach past column k; ER row ``rows[i]``
    reaches ``span[i]`` columns (at most ``width``)."""
    top = np.zeros((n_pad // v_, width + 1), dtype=np.int64)
    np.maximum.at(top, (rows // v_, span), rows % v_ + 1)
    return np.maximum.accumulate(top[:, ::-1], axis=1)[:, ::-1][:, 1:]


def _window_lane_rows(e: EHYB, rows: np.ndarray, tiles: int,
                      far: np.ndarray, cap: int) -> int:
    """How many lane-rows each partition keeps in its window: the count c
    (at most ``cap``) with the least modeled cost.  The kernel pays
    ``ER_PASS_COST`` for each of H = c padded to 8 passes over each of the
    window's ``tiles``; the XLA path pays one element for each slot of its
    (P, E, We) tables, E the most rows of a partition with an entry past
    its c-th lane-row.  ER row ``rows[i]``'s farthest entry sits on its
    partition's ``far[i]``-th lane-row (most entries first)."""
    p_, v_ = e.n_parts, e.vec_size
    need = min(int(far.max()) + 1, cap)
    # rows with an entry past lane-row c, for every c: per partition, a
    # histogram of the rows' farthest lane-rows, summed from the top
    hist = np.bincount((rows // v_) * (need + 1) + np.minimum(far, need),
                       minlength=p_ * (need + 1)).reshape(p_, need + 1)
    past = np.cumsum(hist[:, ::-1], axis=1)[:, ::-1]      # rank >= c
    e_rows = past.max(axis=0)                             # per c in 0..need
    cands = sorted({0, need, *range(8, need, 8)})
    cost = [ER_PASS_COST * (-(-c // 8) * 8) * tiles
            + p_ * (-(-int(e_rows[c]) // 8) * 8) * e.er_width
            for c in cands]
    return cands[int(np.argmin(cost))]


def pack_er_window(e: EHYB, max_lane_rows: int = ER_WINDOW_LANE_ROWS
                   ) -> ERWindow:
    """Pack ``e``'s ER entries into per-partition ER windows (see
    :class:`ERWindow`); ``max_lane_rows`` caps a window below the uint16
    limit (for tests).  Memoized on ``e`` per cap.

    How the ER splits between window and leftover follows from the
    pattern: each partition ranks the lane-rows its ER reads by entries,
    the window keeps the first c of them (``_window_lane_rows``: the cost
    of H passes a window tile against the leftover's XLA gather), and the
    columns of a partition past ``ER_WINDOW_TILE_BYTES`` of its tiles
    join the leftover."""
    cached = getattr(e, "_er_window", None)
    if cached is not None and cached.cap == max_lane_rows:
        return cached
    bump("pack_er_window")
    p_, v_, we = e.n_parts, e.vec_size, e.er_width
    _, sb = lane_geometry(v_)
    tile = sb * LANES
    n128 = -(-e.n_pad // LANES)
    # live entries of the (er_rows, er_width) tables, in their order: ER
    # row by row, each row's entries in column order
    if e.fill_plan is not None:
        live = np.zeros(e.er_vals.size, dtype=bool)
        live[e.fill_plan["er_dst"]] = True
        live = live.reshape(e.er_vals.shape)
    else:
        live = e.er_vals != 0
    slots = np.flatnonzero(live.any(axis=1))          # ER rows with an entry
    cnt = live[slots].sum(axis=1)
    span = we - np.argmax(live[slots, ::-1], axis=1)  # last entry's column + 1
    row = e.er_row_idx[slots].astype(np.int64)
    first = np.cumsum(cnt) - cnt                      # a row's first entry
    kt = np.int32 if p_ * n128 < (1 << 31) else np.int64
    part = np.repeat((row // v_).astype(kt), cnt)
    col = e.er_cols[live]
    n = col.size

    # each partition's lane-rows, ranked by entries (most first).  Equal
    # keys come in runs (a row's entries are in column order): index the
    # runs' keys; every row starts a run
    key = part * kt(n128) + (col >> 7).astype(kt)
    brk = np.ones(n, dtype=bool)
    np.not_equal(key[1:], key[:-1], out=brk[1:])
    brk[first] = True
    run = np.flatnonzero(brk)
    run_len = np.diff(np.append(run, n))
    keys, run_key = np.unique(key[run], return_inverse=True)
    del key, brk, part
    counts = np.bincount(run_key, weights=run_len, minlength=keys.size)
    key_part = keys // n128
    by_count = np.lexsort((keys, -counts, key_part))
    ranked = key_part[by_count]
    rank = np.empty(keys.size, dtype=np.int64)
    rank[by_count] = np.arange(keys.size) - np.searchsorted(ranked, ranked)
    # the window's columns (every ER column a partition's rows reach), cut
    # where a partition's tiles would pass ER_WINDOW_TILE_BYTES
    col_rows = _column_rows(row, span, e.n_pad, v_, we)
    col_tiles = -(-col_rows // tile)
    c = 0
    if n:
        far = np.maximum.reduceat(rank[run_key], np.searchsorted(run, first))
        c = _window_lane_rows(e, row, int(col_tiles.sum()), far,
                              max_lane_rows)
    kept = np.flatnonzero(rank < c)                  # ascending keys
    kept_part = key_part[kept]
    slot_of = np.full(keys.size, -1, dtype=np.int32)
    slot_of[kept] = (np.arange(kept.size)
                     - np.searchsorted(kept_part, kept_part))
    h = -(-c // 8) * 8
    win_rows = np.zeros((p_, h), dtype=np.int32)
    win_rows[kept_part, slot_of[kept]] = keys[kept] % n128
    slot = np.repeat(slot_of[run_key], run_len)
    del run, run_len, run_key

    # the window's entries: on a kept lane-row, in a column that fits
    max_tiles = max(ER_WINDOW_TILE_BYTES // (tile * 6), 1)
    n_cols = (np.cumsum(col_tiles, axis=1) <= max_tiles).sum(axis=1)
    mask = np.zeros(live.shape, dtype=bool)
    mask[live] = slot >= 0
    mask[slots] &= np.arange(we) < n_cols[row // v_][:, None]
    n_win = int(np.count_nonzero(mask))
    width = int(n_cols.max(initial=0)) if n_win else 0
    col_rows = np.where(np.arange(width) < n_cols[:, None],
                        col_rows[:, :width], 0)
    col_starts = np.zeros((p_, width + 1), dtype=np.int32)
    col_starts[:, 1:] = np.cumsum(-(-col_rows // tile), axis=1)
    n_tiles = max(int(col_starts[:, -1].max(initial=0)), 1)
    shape = (p_, n_tiles, sb, LANES)
    plan = {"mask": mask, "slots": slots, "rows": row, "n_pad": e.n_pad}
    col_tab = np.zeros(live.shape, dtype=np.uint16)
    col_tab[live] = ((slot << 7) | (col & (LANES - 1))).astype(np.uint16)
    vals = _window_tiles(e.er_vals, plan, col_starts, shape)
    cols = _window_tiles(col_tab, plan, col_starts, shape)
    del col_tab

    # leftover entries: grouped per partition for the XLA ER path
    n_left = n - n_win
    left = (group_er_by_partition(e, keep=live & ~mask) if n_left
            else None)
    out = ERWindow(cap=max_lane_rows, lane_rows=h, win_rows=win_rows,
                   vals=vals.reshape(shape), cols=cols.reshape(shape),
                   col_starts=col_starts, col_rows=col_rows.astype(np.int32),
                   entries=n_win, leftover=n_left, left=left, plan=plan)
    e._er_window = out
    return out


# ---------------------------------------------------------------------------
# packed "staircase" layout (the TPU kernel's storage)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PackedEHYB:
    """Column-major staircase packing of the sliced-ELL part, in tiles.

    Within a partition, rows are width-sorted (paper Algo 1 l.17), so the
    active cells of column k form a PREFIX of rows [0, R_k).  Column k is
    stored as ceil(R_k / (Sb·128)) ``(Sb, 128)`` tiles starting at tile
    ``col_starts[p, k]``; tile t holds rows [t·Sb·128, (t+1)·Sb·128) of the
    column, lane-dense (row i at sublane (i % (Sb·128)) // 128, lane
    i % 128).  Segments therefore start on tile boundaries, which is what
    lets the TPU kernel load them at aligned dynamic offsets; the padding
    that costs is at most one partial tile per column — the paper's
    sliced-ELL with a slice of Sb·128 rows.  ``packed_len`` is the slots per
    partition (tiles × Sb·128), the same for every partition so one block
    shape covers the kernel.
    """

    base: EHYB
    packed_len: int                   # slots per partition (T · Sb·128)
    packed_vals: np.ndarray           # (P, T, Sb, 128) float
    packed_cols: np.ndarray           # (P, T, Sb, 128) uint16
    col_starts: np.ndarray            # (P, W+1) int32 — first tile of col k
    col_rows: np.ndarray              # (P, W) int32 — active rows R_k
    pack_plan: Optional[dict] = None  # (pi, vi, ki) -> (pi, dest) scatter

    def refill(self, base: "EHYB") -> "PackedEHYB":
        """Re-pack from ``base`` (a value-refilled EHYB on the same pattern)
        by replaying the recorded scatter — no width recomputation."""
        if self.pack_plan is None:
            raise ValueError("this PackedEHYB carries no pack plan")
        p = self.pack_plan
        flat = np.zeros((base.n_parts, self.packed_len),
                        dtype=self.packed_vals.dtype)
        flat[p["pi"], p["dest"]] = base.ell_vals[p["pi"], p["vi"], p["ki"]]
        return dataclasses.replace(
            self, base=base, packed_vals=flat.reshape(self.packed_vals.shape))

    def bytes_moved(self, val_bytes: int = 4, col_bytes: int = 2,
                    space: str = "permuted", fused_er: bool = True,
                    halo_words: Optional[int] = None,
                    n_dev: int = 1, k: int = 1) -> dict:
        b = self.base.bytes_moved(val_bytes, col_bytes, layout="sliced",
                                  space=space, fused_er=fused_er,
                                  halo_words=halo_words, n_dev=n_dev, k=k)
        ell = self.base.n_parts * self.packed_len * (val_bytes + col_bytes)
        return {**b, "ell": ell,
                "total": ell + b["x_cache"] + b["er"] + b["y"] + b["perm"]
                + b["interconnect"]}


def staircase_rows(e: EHYB) -> np.ndarray:
    """(P, W) int32 R_k: rows of each partition whose in-partition width
    exceeds k — column k's active prefix (rows are width-sorted)."""
    p_, v_, w_ = e.n_parts, e.vec_size, e.ell_width
    if e.fill_plan is not None:
        # pattern widths (value-independent: explicit zeros stay packed, so
        # the recorded scatter stays valid across ``refill``)
        widths = e.fill_plan["ell_widths"].reshape(p_, v_)
    else:
        widths = (e.ell_vals != 0).sum(axis=2)           # (P, V) row widths
    hist = np.bincount((np.arange(p_)[:, None] * (w_ + 1) + widths).ravel(),
                       minlength=p_ * (w_ + 1)).reshape(p_, w_ + 1)
    # R_k = #rows with width > k = reverse cumulative count from width k+1
    return np.cumsum(hist[:, ::-1], axis=1)[:, ::-1][:, 1:].astype(np.int32)


def pack_staircase(e: EHYB) -> PackedEHYB:
    """Pack the (P, V, W) tiles column-major into aligned ``(Sb, 128)`` tiles.

    Vectorized as one numpy scatter: cell (p, v, k) is active when
    ``v < col_rows[p, k]`` (rows are width-sorted, so column k's active rows
    are the prefix [0, R_k)), and its destination within partition p's
    flat packed stream is ``col_starts[p, k]·(Sb·128) + v``.  The scatter
    is timed by the ``repro.ehyb.pack`` span, recorded in
    ``preprocess_seconds["pack"]``.
    """
    bump("pack_staircase")
    with span("repro.ehyb.pack") as pack:
        p_, v_, w_ = e.n_parts, e.vec_size, e.ell_width
        col_rows = staircase_rows(e)
        _, sb = lane_geometry(v_)
        tile = sb * LANES
        col_starts = np.zeros((p_, w_ + 1), dtype=np.int32)
        col_starts[:, 1:] = np.cumsum(-(-col_rows // tile), axis=1)
        n_tiles = max(int(col_starts[:, -1].max()), 1)
        pack_l = n_tiles * tile
        packed_vals = np.zeros((p_, pack_l), dtype=e.ell_vals.dtype)
        packed_cols = np.zeros((p_, pack_l), dtype=np.uint16)
        # (P, V, W)
        active = np.arange(v_)[None, :, None] < col_rows[:, None, :]
        pi, vi, ki = np.nonzero(active)
        dest = col_starts[pi, ki].astype(np.int64) * tile + vi
        packed_vals[pi, dest] = e.ell_vals[pi, vi, ki]
        packed_cols[pi, dest] = e.ell_cols[pi, vi, ki]
    e.preprocess_seconds["pack"] = pack.seconds
    shape = (p_, n_tiles, sb, LANES)
    return PackedEHYB(base=e, packed_len=pack_l,
                      packed_vals=packed_vals.reshape(shape),
                      packed_cols=packed_cols.reshape(shape),
                      col_starts=col_starts, col_rows=col_rows,
                      pack_plan={"pi": pi, "dest": dest, "vi": vi, "ki": ki})


# ---------------------------------------------------------------------------
# width-bucketed variant (beyond-paper §Perf optimization)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)     # identity hash: host handle rides in
class EHYBBuckets:                   # jit-static aux data of the device form
    """Partitions grouped into width buckets — one uniform tile per bucket.

    The baseline format pads every partition tile to the *global* max width W;
    on matrices with variable partition density this wastes HBM bytes (the
    quantity the whole paper is about).  Grouping partitions into a few width
    classes and issuing one pallas_call per class removes most padding while
    keeping static BlockSpecs.  GPU EHYB gets the same effect from its dynamic
    warp/slice scheduler (Algo 3), which has no TPU analogue.
    """

    base: EHYB
    # per bucket: (part_ids, vals (B,V,Wb), cols (B,V,Wb))
    part_ids: list        # list[np.ndarray]
    vals: list            # list[np.ndarray]
    cols: list            # list[np.ndarray]
    widths: list          # list[int]

    def bytes_moved(self, val_bytes: int = 4, col_bytes: int = 2,
                    space: str = "permuted", fused_er: bool = True,
                    halo_words: Optional[int] = None,
                    n_dev: int = 1, k: int = 1) -> dict:
        ell = sum(v.size * (val_bytes + col_bytes) for v in self.vals)
        base = self.base.bytes_moved(val_bytes, col_bytes, space=space,
                                     fused_er=fused_er,
                                     halo_words=halo_words, n_dev=n_dev, k=k)
        return {**base, "ell": ell,
                "total": ell + base["x_cache"] + base["er"] + base["y"]
                + base["perm"] + base["interconnect"]}


def build_buckets(e: EHYB, n_buckets: int = 4, lane: int = 8) -> EHYBBuckets:
    """Group partitions by width into ≤ n_buckets classes (equal-count split,
    widths lane-aligned so value tiles stay (8,128)-friendly)."""
    bump("build_buckets")
    order = np.argsort(e.part_widths, kind="stable")
    chunks = np.array_split(order, n_buckets)
    part_ids, vals, cols, widths = [], [], [], []
    for ch in chunks:
        if len(ch) == 0:
            continue
        wb = int(e.part_widths[ch].max())
        wb = max(lane, -(-wb // lane) * lane)
        wb = min(wb, e.ell_width)
        part_ids.append(ch.astype(np.int32))
        vals.append(np.ascontiguousarray(e.ell_vals[ch, :, :wb]))
        cols.append(np.ascontiguousarray(e.ell_cols[ch, :, :wb]))
        widths.append(wb)
    return EHYBBuckets(base=e, part_ids=part_ids, vals=vals, cols=cols,
                       widths=widths)
