"""Pallas TPU kernel for EHYB SpMV/SpMM — the paper's CUDA kernel (Algo 3),
re-derived for the TPU memory hierarchy.

Mapping from the paper's GPU kernel:

  CUDA block ↔ grid step (``B`` partitions per step).
  shared-memory x-slice ↔ the ``(B, Rc, S, 128)`` x block: partition p's
      V = S·128 cached x entries, lane-dense (local column c sits at lane-row
      c // 128, lane c % 128).  Mosaic DMAs it HBM→VMEM once per step and
      double-buffers the next step's slice during this step's compute.
  warp slice (32 rows) ↔ one ``(Sb, 128)`` tile of the packed staircase:
      Sb·128 consecutive rows of one sliced-ELL column (Sb = 8 sublanes, or
      S when a partition has fewer lane-rows).
  uint16 col idx ↔ identical: the uint16 tile stream is the dominant HBM
      stream; widened to int32 in-register.
  atomic slice scheduler ↔ dropped (static grid; balance comes from the
      nnz-balanced partitioner).

The VMEM gather.  Mosaic gathers only inside one vreg
(``tpu.dynamic_gather``: ``take_along_axis`` along the 128 lanes of a row).
A local column index c splits into (hi, lo) = (c >> 7, c & 127): for every
lane-row j of the x-slice the kernel broadcasts row j over the tile, gathers
lanes with ``lo`` and keeps the lanes whose ``hi == j``.  That costs S
gather+select pairs per tile, which is why ``choose_vec_size`` caps S.

The ER ("extra rows") remainder runs through the same kernel as a second
call, from an explicitly cached *ER window* instead of the own slice
(``core.ehyb.ERWindow``): the wrapper in ``ops.py`` gathers each
partition's window, the H lane-rows of x its ER entries read, as whole
128-lane rows, and the kernel gathers the entries inside VMEM.  Its x block
is (B, Rc, H, 128) while the output block stays (B, Rc, S, 128); each tile
pays H gather+select passes, unrolled as for the own slice.  Both calls are
named ``ehyb_packed_spmv``.  Entries the window leaves over
(``core.ehyb.pack_er_window`` chooses H from the pattern, under the uint16
cap of 512 lane-rows) stay in XLA (``core.spmv._fused_er_parts``).

A grid step holds B partitions' A tiles and their x and output blocks in
VMEM, double-buffered; B and the rhs chunk Rc are sized from all three, so
a tall window (H up to 512) takes fewer partitions and rhs columns a step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.partition import LANES, lane_geometry

# VMEM bytes of the packed value+column tiles and the x and output blocks one
# grid step streams in (sizes how many partitions a step handles).  Tunable (repro.tuning SEARCH_SPACE
# "gather_budget") through the wrapper's ``gather_budget`` kwarg.
_GATHER_BUDGET = 4 * 1024 * 1024
# rhs columns per grid step of a multi-rhs apply (SpMM).  Tunable
# ("rhs_chunk") through the wrapper's ``rhs_chunk`` kwarg.
_RHS_CHUNK = 16
# VMEM bytes of one partition's x and output blocks: caps the rhs chunk of a
# call whose x block is tall (an ER window of up to 512 lane-rows holds
# 256 KiB a column), so that a step always fits VMEM.
_XY_BUDGET = 16 * 1024 * 1024


def _largest_divisor_at_most(n: int, cap: int) -> int:
    for d in range(max(1, min(n, cap)), 0, -1):
        if n % d == 0:
            return d
    return 1


def _gather_row(x_ref, b, r, hi, lo, s: int):
    """x[b, r][c] for every local column c = hi·128 + lo of one tile."""
    out = jnp.zeros(lo.shape, jnp.float32)
    for j in range(s):                        # static: one pass per lane-row
        row = jnp.broadcast_to(x_ref[b, r, j:j + 1, :].astype(jnp.float32),
                               lo.shape)
        out = jnp.where(hi == j, jnp.take_along_axis(row, lo, axis=1), out)
    return out


def _ehyb_packed_kernel(starts_ref, rows_ref, x_ref, vals_ref, cols_ref,
                        y_ref, acc_ref, *, n_cols: int, s: int, sb: int):
    """One grid step = B partitions × Rc rhs columns.

    Column k of partition b holds rows [0, R_k) (rows are width-sorted at
    format build), stored as ceil(R_k / (Sb·128)) tiles from tile
    ``starts[b, k]``; tile t of the column feeds output lane-rows
    [t·Sb, (t+1)·Sb).  Each tile gathers from the s lane-rows of its x
    block: the partition's own slice, or its ER window."""
    tile = sb * LANES
    n_b, n_r = x_ref.shape[0], x_ref.shape[1]

    def part(b, carry):
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def column(k, c):
            start = starts_ref[0, b, k]
            n_tiles = (rows_ref[0, b, k] + (tile - 1)) // tile

            def one_tile(t, cc):
                v = vals_ref[b, start + t].astype(jnp.float32)    # (Sb, 128)
                idx = cols_ref[b, start + t].astype(jnp.int32)    # widen
                hi = idx >> 7
                lo = idx & (LANES - 1)
                off = pl.multiple_of(t * sb, sb)
                for r in range(n_r):
                    g = _gather_row(x_ref, b, r, hi, lo, s)
                    acc_ref[r, pl.ds(off, sb), :] += v * g
                return cc

            return jax.lax.fori_loop(0, n_tiles, one_tile, c)

        jax.lax.fori_loop(0, n_cols, column, 0)
        y_ref[b] = acc_ref[...].astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_b, part, 0)


def _packed_call(xl, vals, cols, starts, rows, *, s_out: int,
                 interpret: bool, gather_budget: int | None,
                 rhs_chunk: int | None) -> jnp.ndarray:
    """One ``ehyb_packed_spmv`` call: (P, R, s_out, 128) from the lane-dense
    x blocks ``xl`` (P, R, X, 128), the own slices (X = S) or the ER
    windows (X = H)."""
    p, r, x_rows, _ = xl.shape
    _, t, sb, _ = vals.shape
    w = rows.shape[1]
    xy_col = (x_rows + s_out) * LANES * 4        # x + y bytes per rhs column
    rc = max(1, min(r, _RHS_CHUNK if rhs_chunk is None else rhs_chunk,
                    _XY_BUDGET // xy_col))
    r_pad = -(-r // rc) * rc
    if r_pad != r:
        xl = jnp.pad(xl, ((0, 0), (0, r_pad - r), (0, 0), (0, 0)))
    budget = _GATHER_BUDGET if gather_budget is None else gather_budget
    # one partition's VMEM: its A tiles and its x and output blocks
    per_part = (t * sb * LANES * (vals.dtype.itemsize + cols.dtype.itemsize)
                + rc * xy_col)
    nb = _largest_divisor_at_most(p, budget // per_part)
    vmem = 2 * nb * per_part + rc * s_out * LANES * 4 + (8 << 20)
    kernel = functools.partial(_ehyb_packed_kernel, n_cols=w, s=x_rows,
                               sb=sb)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    tile_spec = pl.BlockSpec((nb, t, sb, LANES), lambda i, c: (i, 0, 0, 0))
    y = pl.pallas_call(
        kernel,
        grid=(p // nb, r_pad // rc),
        in_specs=[
            smem((1, nb, w + 1), lambda i, c: (i, 0, 0)),          # starts
            smem((1, nb, w), lambda i, c: (i, 0, 0)),              # R_k
            pl.BlockSpec((nb, rc, x_rows, LANES), lambda i, c: (i, c, 0, 0)),
            tile_spec, tile_spec],
        out_specs=pl.BlockSpec((nb, rc, s_out, LANES),
                               lambda i, c: (i, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, r_pad, s_out, LANES), xl.dtype),
        scratch_shapes=[pltpu.VMEM((rc, s_out, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(vmem, 100 << 20)),
        interpret=interpret,
        name="ehyb_packed_spmv",
    )(starts.reshape(p // nb, nb, w + 1), rows.reshape(p // nb, nb, w),
      xl, vals, cols)
    return y[:, :r]


def ehyb_packed_pallas(x_parts: jnp.ndarray, packed_vals: jnp.ndarray,
                       packed_cols: jnp.ndarray, col_starts: jnp.ndarray,
                       col_rows: jnp.ndarray, *, er_window: tuple | None = None,
                       interpret: bool = False,
                       gather_budget: int | None = None,
                       rhs_chunk: int | None = None) -> jnp.ndarray:
    """EHYB SpMV/SpMM from the kernel's explicit caches: y_parts (P, V, R).

    x_parts:      (P, V, R) permuted input, partition-major
    packed_vals:  (P, T, Sb, 128) value tiles (see ``core.ehyb.pack_staircase``)
    packed_cols:  (P, T, Sb, 128) uint16 local column tiles
    col_starts:   (P, W+1) int32 first tile of column k
    col_rows:     (P, W) int32 active rows R_k of column k
    er_window:    None, or the ER window's ``(x_win, vals, cols, col_starts,
                  col_rows)`` (``core.ehyb.ERWindow``), x_win (P, R, H, 128)
                  the gathered window: its entries are added by a second
                  call
    """
    p, v, r = x_parts.shape
    _, _, sb, lanes = packed_vals.shape
    s, sb_x = lane_geometry(v)
    if lanes != LANES or sb != sb_x:
        raise ValueError(f"packed tiles {packed_vals.shape[1:]} do not match "
                         f"the lane geometry of vec_size={v}")
    vl = s * LANES
    # lane-dense relayout: (P, V, R) -> (P, R, S, 128)
    xl = jnp.pad(x_parts, ((0, 0), (0, vl - v), (0, 0)))
    xl = jnp.transpose(xl, (0, 2, 1)).reshape(p, r, s, LANES)
    kw = dict(s_out=s, interpret=interpret, gather_budget=gather_budget,
              rhs_chunk=rhs_chunk)
    y = _packed_call(xl, packed_vals, packed_cols, col_starts, col_rows,
                     **kw)
    if er_window is not None:
        x_win, *tables = er_window
        y = y + _packed_call(x_win.astype(xl.dtype), *tables, **kw)
    y = y.reshape(p, r, vl)
    return jnp.transpose(y, (0, 2, 1))[:, :v, :]
