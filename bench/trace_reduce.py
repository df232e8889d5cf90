"""From a profiler trace to the device numbers the per-layer metrics read.

Two steps, kept apart so that the second can be tested on recorded data:

* :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
  neutral record, ``{"device": [[name, start_ns, dur_ns, chip], ...],
  "host": [[name, start_ns, dur_ns], ...], "chips": n}``: the op events of
  the ``XLA Ops`` line of each of the first ``chips`` TPUs (by device id),
  each named by :func:`op_label` and tagged with its chip's place in that
  order, and the harness's own host spans (``bench.*``).  A device event
  without the tag is chip 0's, and a record without ``chips`` is one
  chip's.
* :func:`reduce` turns that record into seconds: the traced window (the
  ``bench.window`` span); per chip, device busy time as the union of op
  intervals in it and each op's self time (time not covered by an op
  nested inside it); the self time of each named kernel, of the
  collectives and of everything else, and the ops that took most time,
  each as the mean over the chips; and the idle gaps, where no chip runs
  an op, each put to the innermost harness span that covers it
  (``host.loop`` where none does).
"""

from __future__ import annotations

import collections
import pathlib

WINDOW_SPAN = "bench.window"
UNCOVERED = "host.loop"
TOP = 10
TPU_PLANE = "/device:TPU:"
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")


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


def tpu_planes(pd, chips=1) -> list:
    """The first ``chips`` TPU planes of a ``ProfileData`` (every one for
    None), in the order of their integer device id (``TPU:10`` comes after
    ``TPU:2``)."""
    ids = {}
    for p in pd.planes:
        rest = p.name[len(TPU_PLANE):]
        if p.name.startswith(TPU_PLANE) and rest.isdigit():
            ids[int(rest)] = p
    return [ids[i] for i in sorted(ids)][:chips]


def extract(xspace_path, kernels=(), span_prefix: str = "bench.",
            chips=1) -> dict:
    """The neutral record of one trace (see the module docstring), device
    ops named by :func:`op_label`; ``chips`` is the number of TPUs read
    (fewer where the trace holds fewer; None: every one)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xspace_path))
    device, host = [], []
    planes = tpu_planes(pd, chips)
    for chip, plane in enumerate(planes):
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                device.append([op_label(ev.name, kernels),
                               float(ev.start_ns), float(ev.duration_ns),
                               chip])
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(span_prefix):
                    host.append([ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)])
    return {"device": device, "host": host, "chips": len(planes)}


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


def is_collective(name: str) -> bool:
    """An op of the exchange between chips: one of :data:`COLLECTIVES`, or
    its ``-start``/``-done`` half, by the op's own name."""
    base = name.split(".", 1)[0]
    return base.removesuffix("-start").removesuffix("-done") in COLLECTIVES


def by_chip(events, chips: int) -> list:
    """``events`` split into one list per chip by their tag (untagged:
    chip 0), with the tag taken off."""
    out = [[] for _ in range(chips)]
    for e in events:
        out[e[3] if len(e) > 3 else 0].append(e[:3])
    return out


def window(record: dict):
    """The traced window ``(lo, hi)`` in ns: the ``bench.window`` span, else
    the span of the device ops of every chip."""
    win = [(s, s + d) for n, s, d in record.get("host", [])
           if n == WINDOW_SPAN]
    dev = record.get("device", [])
    if win:
        return win[0]
    if dev:
        return (min(e[1] for e in dev), max(e[1] + e[2] for e in dev))
    return 0.0, 0.0


def reduce(record: dict, kernels=()) -> dict:
    """Seconds of the traced window, busy time, kernels, collectives, the
    rest, top ops and idle gaps (see the module docstring).  A kernel
    matches an op named after it (its name, or its name and ``.<n>``)."""
    chips = record.get("chips", 1)
    spans = [(n, s, s + d) for n, s, d in record.get("host", [])]
    lo, hi = window(record)
    per_chip = [_clip(ev, lo, hi)
                for ev in by_chip(record.get("device", []), chips)]
    busy_ns = [sum(e - s for s, e in _union([(s, e) for _, s, e in ops]))
               for ops in per_chip]
    by_name = collections.Counter()
    kernel_ns = {k: 0.0 for k in kernels}
    other_ns = collective_ns = 0.0
    for ops in per_chip:
        for name, t in _self_times(ops):
            by_name[name] += t
            hit = next((k for k in kernels if _is_kernel(name, k)), None)
            if hit is not None:
                kernel_ns[hit] += t
            else:
                other_ns += t
                if is_collective(name):
                    collective_ns += t
    busy = _union([(s, e) for ops in per_chip for _, s, e in ops])
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
    per = 1e-9 / max(chips, 1)
    mean = collections.Counter({n: t * per for n, t in by_name.items()})
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_ns) * per,
        "busy_s_by_chip": [b * 1e-9 for b in busy_ns],
        "n_device_ops": sum(len(ops) for ops in per_chip),
        "kernel_s": {k: v * per for k, v in kernel_ns.items()},
        "other_s": other_ns * per,
        "collective_s": collective_ns * per,
        "device_ops": [[n, t] for n, t in mean.most_common(TOP)],
        "idle_gaps": [[n, t * 1e-9] for n, t in gaps.most_common(TOP)],
    }
