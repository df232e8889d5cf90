"""Jit'd public wrappers around the Pallas EHYB kernel.

``interpret=None`` (default) resolves per backend: the Pallas interpreter
on CPU (exact, for validation), compiled through Mosaic on TPU.  Pass an
explicit bool to override.

One apply = the ``ehyb_packed_spmv`` kernel over each partition's own
x-slice and, in a second call, over its ER window (the lane-rows of x its ER
entries read, gathered here as whole 128-lane rows), accumulated into the
same per-partition (V, R) block.  ER entries a window leaves over stay in
XLA (``core.spmv._fused_er_parts``); stencil and FEM matrices leave none,
and that stage is then dropped statically.  The ``*_permuted``
variant consumes/produces permuted-space vectors so solver loops skip the
per-call pad/``perm``/``inv_perm`` gathers entirely; an (n_pad, K) rhs runs
the same kernel with the A tiles streamed once for all K columns.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.partition import LANES
from ..core.spmv import _as_2d, _from_permuted, _fused_er_parts, _to_permuted
from . import ehyb_spmv as _k


def _resolve_interpret(interpret):
    """None -> backend default (trace-time): interpreter on CPU, compiled
    elsewhere.  The autotuner never *selects* interpreter-backed formats on
    CPU, but forced builds and kernel tests still run there."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


# ---------------------------------------------------------------------------
# per-backend capability probe (the guarded-apply chain keys off this)
# ---------------------------------------------------------------------------

_PALLAS_OK: dict = {}


def backend_supports_pallas(backend: str | None = None) -> bool:
    """Can a trivial ``pallas_call`` lower, compile, and run correctly on
    ``backend`` (default: the current one)?

    Cached per (backend, chaos epoch): ``reliability.chaos`` can force the
    probe to fail — and its epoch bump on exit re-arms the real answer.
    A False here short-circuits every Pallas level of the guarded-apply
    fallback chain without paying one doomed compile per plan.  On a TPU
    with no fault injection armed a failure is never an answer: the probe
    raises, since Pallas is the path the format promises there."""
    import numpy as np

    # function imports (the package attr `chaos` shadows the submodule)
    from ..reliability.chaos import active as _chaos_active
    from ..reliability.chaos import check_kernel as _chaos_check
    from ..reliability.chaos import epoch as _chaos_epoch

    backend = backend or jax.default_backend()
    key = (backend, _chaos_epoch())
    hit = _PALLAS_OK.get(key)
    if hit is not None:
        return hit

    def probe():
        from jax.experimental import pallas as pl

        def _double(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
        y = pl.pallas_call(
            _double, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=_resolve_interpret(None))(x)
        return bool(np.allclose(np.asarray(jax.block_until_ready(y)),
                                np.asarray(x) * 2.0))

    try:
        _chaos_check("pallas:probe")
        from ..api.plan import _run_untraced

        # concrete values even when the guard resolves inside a trace
        ok = _run_untraced(probe)
    except Exception as e:  # noqa: BLE001 — capability probe: ANY lowering,
        # compile or execution error (jax raises many types) means pallas
        # is unusable on this backend
        if backend == "tpu" and _chaos_active() is None:
            raise RuntimeError(f"pallas probe failed on the TPU: {e}") from e
        ok = False
    if not ok and backend == "tpu" and _chaos_active() is None:
        raise RuntimeError("pallas probe returned wrong values on the TPU")
    _PALLAS_OK[key] = ok
    return ok


def _er_window_x(x2: jax.Array, win_rows: jax.Array) -> jax.Array:
    """(P, R, H, 128): each partition's ER window of x (n_pad, R), gathered
    as whole 128-lane rows of the flat, 128-padded vector."""
    with jax.named_scope("repro.er.window"):
        n, r = x2.shape
        rows = -(-n // LANES)
        # rhs-major first, so the gathered rows keep 128 lanes minor
        xr = jnp.pad(x2.T, ((0, 0), (0, rows * LANES - n)))
        return jnp.transpose(xr.reshape(r, rows, LANES)[:, win_rows],
                             (1, 0, 2, 3))


@partial(jax.jit, static_argnames=("interpret",))
def ehyb_spmv_packed_pallas_permuted(m, x_new: jax.Array, *,
                                     interpret: bool | None = None
                                     ) -> jax.Array:
    """Packed EHYB SpMV/SpMM in the permuted space.

    m: core.spmv.EHYBPackedDevice. x_new: (n_pad,) or (n_pad, R).

    Tuned kernel parameters ride the container's static ``kparams`` aux
    (``repro.tuning.TunedParams.token()``): read here at trace time, they
    specialize the compiled program — and because they are part of the
    pytree treedef, a differently-tuned operator can never hit this jit
    cache entry."""
    interpret = _resolve_interpret(interpret)
    kp = dict(getattr(m, "kparams", ()) or ())
    x2, squeeze = _as_2d(x_new)
    r = x2.shape[1]
    window = None
    if m.win_vals is not None:
        window = (_er_window_x(x2, m.win_rows), m.win_vals, m.win_cols,
                  m.win_starts, m.win_col_rows)
    y_parts = _k.ehyb_packed_pallas(
        x2.reshape(m.n_parts, m.vec_size, r), m.packed_vals, m.packed_cols,
        m.col_starts, m.col_rows, er_window=window, interpret=interpret,
        gather_budget=kp.get("gather_budget"), rhs_chunk=kp.get("rhs_chunk"))
    if m.er_p_vals is not None:                   # the window's leftover
        y_parts = y_parts + _fused_er_parts(
            x2, m.er_p_vals, m.er_p_cols, m.er_p_rows,
            m.vec_size).astype(y_parts.dtype)
    y_new = y_parts.reshape(m.n_pad, r)
    return y_new[:, 0] if squeeze else y_new


@partial(jax.jit, static_argnames=("interpret",))
def ehyb_spmv_packed_pallas(m, x: jax.Array, *,
                            interpret: bool | None = None) -> jax.Array:
    """Packed EHYB SpMV/SpMM, original space. m: EHYBPackedDevice.
    x: (n,) or (n, R)."""
    x_new, squeeze = _to_permuted(m, x)
    y_new = ehyb_spmv_packed_pallas_permuted(m, x_new, interpret=interpret)
    return _from_permuted(m, y_new, squeeze)
