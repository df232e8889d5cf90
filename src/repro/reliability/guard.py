"""Guarded apply: per-backend capability probe + ordered fallback chain.

Jitted code cannot ``try/except`` a lowering failure, so all recovery
happens at host dispatch: :func:`guarded_apply` wraps a plan's raw
``(obj, x) -> y`` closure in a :class:`_Guard` that lazily resolves which
level of the format's fallback chain actually executes on this backend:

    native apply  ->  lax/gather reference

* the **native** level is the format's registered apply (the Pallas kernel
  for ``ehyb_packed``; already-XLA applies for the rest);
* the **reference** level is format-independent: gather/scatter-add over
  the plan's COO pattern with values recovered through the probed value
  maps — it lowers anywhere XLA does, so the chain always terminates.

On a TPU with no fault injection armed there is no fallback: a native
level that fails to lower or run raises.  The TPU is where the format's
kernel is the point, and a silent reference apply under the format's name
would hide exactly that failure (and time the wrong code in a benchmark).

Resolution probes a level by running it once on the plan's concrete
template container with a zero vector (on the ``_run_untraced`` worker, so
resolution triggered mid-trace stays trace-free); a raise — organic or
chaos-injected — moves to the next level.  Pure-XLA chains skip the probe
unless chaos is armed (zero overhead on the hot dispatch path: the cost
model's <5% api_overhead gate still holds).  The resolved level is cached
on the guard until the chaos epoch moves; a downgrade is recorded on the
``Plan`` (``plan.degraded``), counted (``guard.downgrade`` in
``core.counters``), and warned exactly once per (pattern, kind).
"""

from __future__ import annotations

import warnings

import numpy as np

from ..core.counters import bump
# NOTE: import the functions, not `from . import chaos` — the package
# re-exports the chaos() context manager under the submodule's name, so the
# package attribute shadows the module object
from .chaos import active as _chaos_active
from .chaos import check_kernel as _chaos_check_kernel
from .chaos import epoch as _chaos_epoch
from .policy import ReliabilityWarning

_WARNED: set = set()


def reset_warned() -> None:
    _WARNED.clear()


# ---------------------------------------------------------------------------
# reference level (format-independent, always-lowerable)
# ---------------------------------------------------------------------------

def reference_apply(plan, kind: str = "apply"):
    """The lax/gather CSR reference ``(obj, x) -> y`` for ``plan``.

    Trace-safe: values are recovered from the (possibly traced) container
    through the plan's value maps; the pattern's (rows, cols) stay host
    constants.  ``kind="permuted"`` wraps the same product in the
    container's perm/pad round trip so it is a drop-in for the permuted
    hot-loop apply."""
    rows, cols = plan.coo()
    n = plan.n

    def _csr(vals, x2):
        import jax.numpy as jnp

        acc = jnp.promote_types(jnp.result_type(vals.dtype, x2.dtype),
                                jnp.float32)
        contrib = vals[:, None].astype(acc) * x2[cols].astype(acc)
        y = jnp.zeros((n, x2.shape[1]), acc).at[rows].add(contrib)
        return y.astype(x2.dtype)

    def ref(obj, x):
        import jax.numpy as jnp

        from ..core.spmv import _as_2d

        plan._ensure_value_maps()
        vals = plan.values_of(obj)
        x2, squeeze = _as_2d(jnp.asarray(x))
        if kind == "permuted":
            from ..core.spmv import _from_permuted, _to_permuted

            xo = _from_permuted(obj, x2, False)
            yn, _ = _to_permuted(obj, _csr(vals, xo))
            return yn[:, 0] if squeeze else yn
        y = _csr(vals, x2)
        return y[:, 0] if squeeze else y

    return ref


def fallback_chain(plan, tpl, kind: str):
    """Ordered ``(name, fn, needs_pallas)`` levels for ``plan``/``kind``."""
    from ..autotune.registry import get_format

    native = tpl.apply if kind == "apply" else tpl.apply_permuted
    pallas_native = get_format(plan.format).kernel != "xla"
    return [(f"{plan.format}:native", native, pallas_native),
            ("reference", reference_apply(plan, kind), False)]


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# the guard
# ---------------------------------------------------------------------------

class _Guard:
    """Stable-identity wrapper around a plan's apply (jit caches key on it).

    Per-call cost on the resolved path: one epoch compare + one attribute
    load before the underlying closure runs."""

    __slots__ = ("plan", "tpl", "kind", "level", "chain", "_fn", "_epoch")

    def __init__(self, plan, tpl, kind: str):
        self.plan, self.tpl, self.kind = plan, tpl, kind
        self.level = None          # resolved level name
        self.chain = ()            # level names, primary first
        self._fn = None
        self._epoch = -1

    def __call__(self, obj, x):
        if self._fn is None or self._epoch != _chaos_epoch():
            self._resolve()
        y = self._fn(obj, x)
        c = _chaos_active()
        if c is not None:
            y = c.corrupt_output(y, self.level)
        return y

    @property
    def _cache_size(self):
        """Delegate jax's jit cache-size probe to the resolved level, so the
        zero-recompilation tests keep observing the underlying jit cache
        through the guard."""
        if self._fn is None or self._epoch != _chaos_epoch():
            self._resolve()
        return getattr(self._fn, "_cache_size", None)

    # ---- resolution --------------------------------------------------------

    def _probe(self, fn) -> None:
        """Execute ``fn`` once, concretely, on the template container."""
        from ..api.plan import _run_untraced

        tpl = self.tpl

        def go():
            import jax
            import jax.numpy as jnp

            n = tpl.obj.n_pad if self.kind == "permuted" else self.plan.n
            y = jax.block_until_ready(fn(tpl.obj, jnp.zeros((n,),
                                                            jnp.float32)))
            if not bool(np.isfinite(np.asarray(y)).all()):
                raise FloatingPointError(
                    "capability probe produced non-finite output")

        _run_untraced(go)

    def _resolve(self) -> None:
        ep = _chaos_epoch()
        levels = fallback_chain(self.plan, self.tpl, self.kind)
        self.chain = tuple(name for name, _, _ in levels)
        must_probe = _chaos_active() is not None
        failures = []
        chosen = None
        for i, (name, fn, needs_pallas) in enumerate(levels):
            last = i == len(levels) - 1
            try:
                if not last:            # the reference level is exempt
                    _chaos_check_kernel(name)
                if needs_pallas:
                    from ..kernels.ops import backend_supports_pallas

                    if not backend_supports_pallas():
                        raise RuntimeError(
                            "pallas kernels unavailable on this backend")
                if (needs_pallas or must_probe) and not last:
                    self._probe(fn)
                chosen = (name, fn)
                break
            except Exception as e:      # noqa: BLE001 — any lowering error
                if _chaos_active() is None and _on_tpu():
                    raise               # no silent degradation on the chip
                bump("guard.level_failed")
                failures.append((name, e))
        if chosen is None:
            name, err = failures[-1]
            raise RuntimeError(
                f"guarded apply: every fallback level failed for plan "
                f"{self.plan.key} ({self.kind}); last level {name!r}: {err}"
            ) from err
        self.level, self._fn = chosen
        self._epoch = ep
        if failures:
            bump("guard.downgrade")
            bump(f"guard.downgrade.{self.plan.format}")
            wkey = (self.plan.key, self.kind)
            if wkey not in _WARNED:
                _WARNED.add(wkey)
                tried = "; ".join(f"{n}: {type(e).__name__}: {e}"
                                  for n, e in failures)
                warnings.warn(
                    f"plan {self.plan.key} ({self.plan.format!r}, "
                    f"{self.kind}) degraded to fallback level "
                    f"{self.level!r} after: {tried}",
                    ReliabilityWarning, stacklevel=3)


def guarded_apply(plan, tpl, kind: str):
    """The (cached, stable-identity) guard wrapping ``plan``'s ``kind``
    apply — the hook ``api.plan.Plan._raw_apply*`` routes through."""
    g = plan._guards.get(kind)
    if g is None:
        g = plan._guards[kind] = _Guard(plan, tpl, kind)
    return g
