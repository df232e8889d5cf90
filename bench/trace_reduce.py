"""From a profiler trace to the device numbers the per-layer metrics read.

Two steps, kept apart so that the second can be tested on recorded data:

* :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
  neutral record, ``{"device": [[name, start_ns, dur_ns], ...], "host":
  [[name, start_ns, dur_ns], ...]}``: the op events of the first TPU's
  ``XLA Ops`` line, each named by :func:`op_label`, and the harness's own
  host spans (``bench.*``).
* :func:`reduce` turns that record into seconds: the traced window (the
  ``bench.window`` span), device busy time as the union of op intervals in
  it, each op's self time (time not covered by an op nested inside it),
  the self time of each named kernel and of everything else, the ops that
  took most time, and the idle gaps, each put to the innermost harness
  span that covers it (``host.loop`` where none does).
"""

from __future__ import annotations

import collections
import pathlib

WINDOW_SPAN = "bench.window"
UNCOVERED = "host.loop"
TOP = 10


def find_xspace(logdir) -> pathlib.Path:
    """The one ``*.xplane.pb`` a ``jax.profiler`` trace left in ``logdir``."""
    found = sorted(pathlib.Path(logdir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def op_label(text: str, kernels=()) -> str:
    """A device op's short name, from the op's own identity only: a kernel
    when the HLO instruction is named after it (``%ehyb_packed_spmv.9 =
    ...``) or is a custom call whose ``op_name`` it is, else the
    instruction's name (the text before `` = ``, without ``%``).  A kernel
    named only among the operands does not count: that op consumes the
    kernel's output."""
    name = text.split(" = ", 1)[0].lstrip("%")
    call = text.split(" = ", 1)[-1]
    for k in kernels:
        if _is_kernel(name, k) or ("custom-call(" in call
                                   and f'op_name="{k}"' in call):
            return k
    return name


def _is_kernel(name: str, kernel: str) -> bool:
    return name == kernel or name.startswith(kernel + ".")


def extract(xspace_path, kernels=(), span_prefix: str = "bench.") -> dict:
    """The neutral record of one trace (see the module docstring), device
    ops named by :func:`op_label`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xspace_path))
    device, host = [], []
    tpus = sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)
    if tpus:
        for line in tpus[0].lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                device.append([op_label(ev.name, kernels),
                               float(ev.start_ns), float(ev.duration_ns)])
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(span_prefix):
                    host.append([ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)])
    return {"device": device, "host": host}


def _clip(events, lo, hi):
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _self_times(events):
    """``[(name, self_ns)]`` of ``(name, start, end)`` intervals on one
    line: an op's time less the time of ops nested inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [e[2] - e[1] for e in events]
    stack = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            self_ns[parent] -= min(e, events[parent][2]) - s
        stack.append(i)
    return [(events[i][0], self_ns[i]) for i in range(len(events))]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(record: dict, kernels=()) -> dict:
    """Seconds of the traced window, busy time, kernels, the rest, top ops
    and idle gaps (see the module docstring).  A kernel matches an op named
    after it (its name, or its name and ``.<n>``)."""
    host = record.get("host", [])
    spans = [(n, s, s + d) for n, s, d in host]
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    dev = record.get("device", [])
    if win:
        lo, hi = win[0]
    elif dev:
        lo = min(s for _, s, _ in dev)
        hi = max(s + d for _, s, d in dev)
    else:
        lo = hi = 0.0
    ops = _clip(dev, lo, hi)
    busy = _union([(s, e) for _, s, e in ops])
    busy_ns = sum(e - s for s, e in busy)
    by_name = collections.Counter()
    kernel_ns = {k: 0.0 for k in kernels}
    other_ns = 0.0
    for name, t in _self_times(ops):
        by_name[name] += t
        hit = next((k for k in kernels if _is_kernel(name, k)), None)
        if hit is None:
            other_ns += t
        else:
            kernel_ns[hit] += t
    gaps = collections.Counter()
    inner = sorted(((n, s, e) for n, s, e in spans if n != WINDOW_SPAN),
                   key=lambda t: t[2] - t[1])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = 0.5 * (g0 + g1)
        cover = next((n for n, s, e in inner if s <= mid <= e), UNCOVERED)
        gaps[cover] += g1 - g0
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "n_device_ops": len(ops),
        "kernel_s": {k: v * 1e-9 for k, v in kernel_ns.items()},
        "other_s": other_ns * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in by_name.most_common(TOP)],
        "idle_gaps": [[n, t * 1e-9] for n, t in gaps.most_common(TOP)],
    }
