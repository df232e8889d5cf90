"""Work counters, program spans and compile counters.

Every host-side structure pass (partitioning, EHYB build, staircase packing,
ER grouping) and every value-only refill increments a named counter here, so
tests and benchmarks can assert *which* work a code path triggered — in
particular, that ``update_values``/refill paths run zero partitioning or
packing passes (the amortization claim of the paper's §6, made checkable).

:func:`span` times a stage of eager host code.  It opens a
``jax.profiler.TraceAnnotation`` of the same name, so that under a profiler
trace the stage lies on the clock of the device's ops, and it adds its
duration to the table that :func:`timings` returns.  Spans nest per thread;
a worker thread takes over its caller's open span through :func:`carry`.
No span runs per apply or inside a traced function.

Once :func:`start_compile_counters` has run (``enable_compile_cache`` calls
it), JAX's own compile events land in the same table: ``jax.lower``
(tracing to a jaxpr and lowering to MLIR), ``jax.compile`` (backend
compile, persistent-cache loads included), ``jax.cache_hits`` (calls =
hits, seconds = the loads) and ``jax.cache_misses`` (entries written to the
persistent cache).
"""

from __future__ import annotations

import threading
import time
from collections import Counter

import jax

COUNTERS: Counter = Counter()
# name -> {"seconds": total, "self": total less child spans, "calls": n,
#          "parent": the span open around the last call ("" at top level)}
TIMINGS: dict = {}
_LOCK = threading.Lock()
_LOCAL = threading.local()


def bump(name: str, n: int = 1) -> None:
    COUNTERS[name] += n


def snapshot() -> dict:
    return dict(COUNTERS)


def timings() -> dict:
    """A copy of the span table (see :data:`TIMINGS`)."""
    with _LOCK:
        return {k: dict(v) for k, v in TIMINGS.items()}


def reset() -> None:
    COUNTERS.clear()
    with _LOCK:
        TIMINGS.clear()


def _add(name: str, seconds: float, own: float, parent: str,
         calls: int = 1) -> None:
    with _LOCK:
        t = TIMINGS.get(name)
        if t is None:
            t = TIMINGS[name] = {"seconds": 0.0, "self": 0.0, "calls": 0,
                                 "parent": parent}
        t["seconds"] += seconds
        t["self"] += own
        t["calls"] += calls
        t["parent"] = parent


def _stack() -> list:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


def _open_name() -> str:
    st = _stack()
    return st[-1].name if st else ""


class span:
    """``with span(name, **meta) as s:`` — time the block; ``s.seconds``
    holds its duration once it has closed.  ``meta`` goes to the trace
    annotation only (for example a request id shared by a solve's spans)."""

    __slots__ = ("name", "seconds", "_child", "_t0", "_note")

    def __init__(self, name: str, **meta):
        self.name = name
        self.seconds = 0.0
        self._child = 0.0
        self._note = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self) -> "span":
        _stack().append(self)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = self.seconds = time.perf_counter() - self._t0
        self._note.__exit__(*exc)
        st = _stack()
        st.pop()
        parent = st[-1] if st else None
        if parent is not None:
            parent._child += dt
        _add(self.name, dt, dt - self._child,
             parent.name if parent is not None else "")


def carry(fn):
    """``fn`` wrapped to run under the calling thread's open spans on
    whichever thread calls the wrapper (the caller waits for it), so that
    the spans ``fn`` opens nest as if on the caller's thread."""
    outer = list(_stack())

    def run():
        st = _stack()
        saved = st[:]
        st[:] = outer
        try:
            return fn()
        finally:
            st[:] = saved

    return run


_COMPILE_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.lower",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_hits",
}
_COMPILE_EVENTS = {"/jax/compilation_cache/cache_misses": "jax.cache_misses"}


def _on_duration(event: str, seconds: float, **_) -> None:
    name = _COMPILE_DURATIONS.get(event)
    if name is not None:
        _add(name, seconds, seconds, _open_name())


def _on_event(event: str, **_) -> None:
    name = _COMPILE_EVENTS.get(event)
    if name is not None:
        _add(name, 0.0, 0.0, _open_name())


# kept across a reload of this module, whose listeners would add twice
_LISTENING = globals().get("_LISTENING", False)


def start_compile_counters() -> None:
    """Register the compile listeners with ``jax.monitoring``, once per
    process."""
    global _LISTENING
    if _LISTENING:
        return
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _LISTENING = True
