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

The ER ("extra rows") remainder is not in this kernel: it is computed by XLA
(``core.spmv._fused_er_parts``) and added by the wrapper in ``ops.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.partition import LANES, lane_geometry

# VMEM bytes of packed value+column tiles one grid step streams in (sizes how
# many partitions a step handles).  Tunable (repro.tuning SEARCH_SPACE
# "gather_budget") through the wrapper's ``gather_budget`` kwarg.
_GATHER_BUDGET = 4 * 1024 * 1024
# rhs columns per grid step of a multi-rhs apply (SpMM).  Tunable
# ("rhs_chunk") through the wrapper's ``rhs_chunk`` kwarg.
_RHS_CHUNK = 16


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
    [t·Sb, (t+1)·Sb)."""
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


def ehyb_packed_pallas(x_parts: jnp.ndarray, packed_vals: jnp.ndarray,
                       packed_cols: jnp.ndarray, col_starts: jnp.ndarray,
                       col_rows: jnp.ndarray, *, interpret: bool = False,
                       gather_budget: int | None = None,
                       rhs_chunk: int | None = None) -> jnp.ndarray:
    """Cached (sliced-ELL) part of EHYB SpMV/SpMM: y_parts (P, V, R).

    x_parts:      (P, V, R) permuted input, partition-major
    packed_vals:  (P, T, Sb, 128) value tiles (see ``core.ehyb.pack_staircase``)
    packed_cols:  (P, T, Sb, 128) uint16 local column tiles
    col_starts:   (P, W+1) int32 first tile of column k
    col_rows:     (P, W) int32 active rows R_k of column k
    """
    p, v, r = x_parts.shape
    _, t, sb, lanes = packed_vals.shape
    s, sb_x = lane_geometry(v)
    if lanes != LANES or sb != sb_x:
        raise ValueError(f"packed tiles {packed_vals.shape[1:]} do not match "
                         f"the lane geometry of vec_size={v}")
    w = col_rows.shape[1]
    vl = s * LANES
    # lane-dense relayout: (P, V, R) -> (P, R, S, 128)
    xl = jnp.pad(x_parts, ((0, 0), (0, vl - v), (0, 0)))
    xl = jnp.transpose(xl, (0, 2, 1)).reshape(p, r, s, LANES)
    rc = max(1, min(r, _RHS_CHUNK if rhs_chunk is None else rhs_chunk))
    r_pad = -(-r // rc) * rc
    if r_pad != r:
        xl = jnp.pad(xl, ((0, 0), (0, r_pad - r), (0, 0), (0, 0)))
    budget = _GATHER_BUDGET if gather_budget is None else gather_budget
    per_part = t * sb * LANES * (packed_vals.dtype.itemsize
                                 + packed_cols.dtype.itemsize)
    nb = _largest_divisor_at_most(p, budget // max(per_part, 1))
    x_bytes = nb * rc * vl * 4
    vmem = 2 * (nb * per_part + 2 * x_bytes) + rc * vl * 4 + (8 << 20)
    kernel = functools.partial(_ehyb_packed_kernel, n_cols=w, s=s, sb=sb)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    y = pl.pallas_call(
        kernel,
        grid=(p // nb, r_pad // rc),
        in_specs=[
            smem((1, nb, w + 1), lambda i, c: (i, 0, 0)),          # starts
            smem((1, nb, w), lambda i, c: (i, 0, 0)),              # R_k
            pl.BlockSpec((nb, rc, s, LANES), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((nb, t, sb, LANES), lambda i, c: (i, 0, 0, 0)),
            pl.BlockSpec((nb, t, sb, LANES), lambda i, c: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((nb, rc, s, LANES),
                               lambda i, c: (i, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, r_pad, s, LANES), x_parts.dtype),
        scratch_shapes=[pltpu.VMEM((rc, s, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(vmem, 100 << 20)),
        interpret=interpret,
        name="ehyb_packed_spmv",
    )(col_starts.reshape(p // nb, nb, w + 1), col_rows.reshape(p // nb, nb, w),
      xl, packed_vals, packed_cols)
    y = y[:, :r].reshape(p, r, vl)
    return jnp.transpose(y, (0, 2, 1))[:, :v, :]
