"""Pure-jnp oracles for the Pallas kernels.

Each oracle is the mathematically transparent version of what the kernel
computes, written with plain jnp ops (no pallas, no tricks).  Kernel tests
sweep shapes/dtypes and assert allclose against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ehyb_ell_ref(x_parts: jnp.ndarray, ell_vals: jnp.ndarray,
                 ell_cols: jnp.ndarray) -> jnp.ndarray:
    """Cached (sliced-ELL) part of EHYB.

    x_parts:  (P, V, R) — partitioned input vector(s), reordered space
    ell_vals: (P, V, W)
    ell_cols: (P, V, W) integer local indices in [0, V)
    returns   (P, V, R)
    """
    def one(xv, vals, cols):
        g = xv[cols.astype(jnp.int32)]               # (V, W, R)
        return jnp.einsum("vw,vwr->vr", vals, g)

    return jax.vmap(one)(x_parts, ell_vals, ell_cols)


def er_ref(x_new: jnp.ndarray, er_vals: jnp.ndarray,
           er_cols: jnp.ndarray) -> jnp.ndarray:
    """Uncached ER part: global gather + row dot.

    x_new: (n_pad, R); er_vals: (Rr, W); er_cols: (Rr, W) global indices.
    returns (Rr, R) per-ER-slot partial sums (caller scatters by er_row_idx).
    """
    g = x_new[er_cols]                                # (Rr, W, R)
    return jnp.einsum("ew,ewr->er", er_vals, g)
