"""Fused Pallas CG-step kernel: the Krylov loop's vector updates in one pass.

A plain jnp CG body pays 4–6 separate HBM passes over the solution-sized
vectors per iteration (x-axpy, r-axpy, the preconditioner apply, and two dot
reductions).  This kernel performs

    x' = x + alpha·p
    r' = r - alpha·ap
    z' = minv ⊙ r'            (diagonal preconditioner)
    rz = <r', z'>,  rr = <r', r'>

in a single grid sweep: the vectors are viewed lane-dense as (rows, 128),
each step streams one (tile_rows, 128) block of (x, r, p, ap, minv) from HBM,
writes the updated blocks, and folds both dot products into a revisited
(2, 8, 128) block of vreg-shaped partial sums (TPU grid steps are
sequential, so read-modify-write accumulation across steps is well-defined —
the standard Pallas reduction pattern); the last 2·1024-element reduction is
left to XLA.  ``rr`` carried in solver loop state is what lets the
``while_loop`` condition avoid an extra full-vector norm pass.

Like the SpMV kernel, ``interpret=True`` (CPU default) validates the body;
on TPU it is compiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
_TILE_ROWS = 512          # (512, 128) f32 blocks: 256 KiB per vector


def _cg_update_kernel(alpha_ref, x_ref, r_ref, p_ref, ap_ref, minv_ref,
                      xo_ref, ro_ref, zo_ref, dots_ref):
    i = pl.program_id(0)
    alpha = alpha_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    ap = ap_ref[...].astype(jnp.float32)
    xn = x_ref[...].astype(jnp.float32) + alpha * p
    rn = r_ref[...].astype(jnp.float32) - alpha * ap
    zn = minv_ref[...].astype(jnp.float32) * rn
    xo_ref[...] = xn.astype(xo_ref.dtype)
    ro_ref[...] = rn.astype(ro_ref.dtype)
    zo_ref[...] = zn.astype(zo_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dots_ref[...] = jnp.zeros(dots_ref.shape, jnp.float32)

    fold = (-1, _SUBLANES, _LANES)
    dots_ref[0] += jnp.sum((rn * zn).reshape(fold), axis=0)
    dots_ref[1] += jnp.sum((rn * rn).reshape(fold), axis=0)


def fused_cg_update(x: jax.Array, r: jax.Array, p: jax.Array,
                    ap: jax.Array, minv: jax.Array, alpha: jax.Array,
                    *, interpret: bool | None = None):
    """One fused pass: returns (x', r', z', rz, rr).

    All of x, r, p, ap, minv are (n,); alpha is a scalar.  Zero padding to a
    tile multiple is benign: padded lanes of r' are 0 - alpha·0 = 0 and
    contribute nothing to either dot.

    ``interpret=None`` (default) resolves per backend: compiled through
    Mosaic on TPU, interpreter (validation) elsewhere — the revisited-block
    dots accumulation assumes the sequential TPU grid and would race on a
    parallel GPU grid, so only TPU gets the compiled path.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _fused_cg_update(x, r, p, ap, minv, alpha, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_cg_update(x, r, p, ap, minv, alpha, *, interpret: bool):
    n = x.shape[0]
    rows = -(-n // _LANES)
    tile = min(_TILE_ROWS, -(-rows // _SUBLANES) * _SUBLANES)
    tiles = -(-rows // tile)
    pad = tiles * tile * _LANES - n

    def lanes(v):
        return jnp.pad(v, (0, pad)).reshape(tiles * tile, _LANES)

    alpha2 = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    vec = pl.BlockSpec((tile, _LANES), lambda i: (i, 0))
    shape = (tiles * tile, _LANES)
    xn, rn, zn, dots = pl.pallas_call(
        _cg_update_kernel,
        grid=(tiles,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [vec] * 5,
        out_specs=[vec, vec, vec,
                   pl.BlockSpec((2, _SUBLANES, _LANES), lambda i: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(shape, x.dtype),
                   jax.ShapeDtypeStruct(shape, r.dtype),
                   jax.ShapeDtypeStruct(shape, r.dtype),
                   jax.ShapeDtypeStruct((2, _SUBLANES, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_cg_update",
    )(alpha2, lanes(x), lanes(r), lanes(p), lanes(ap), lanes(minv))
    dots = jnp.sum(dots, axis=(1, 2))
    return (xn.reshape(-1)[:n], rn.reshape(-1)[:n], zn.reshape(-1)[:n],
            dots[0], dots[1])
