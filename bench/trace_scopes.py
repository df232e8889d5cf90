#!/usr/bin/env python3
"""The program's own spans and device scopes in a profiler trace.

An addition to :mod:`bench.trace_reduce` that changes none of its numbers:

* :func:`extract` reads a trace as ``trace_reduce.extract`` does, keeps the
  program's host spans (``repro.*``, opened by ``repro.core.counters.span``)
  beside the harness's (``bench.*``), and gives each device op its
  innermost ``repro.*`` scope (``jax.named_scope`` in the program), or
  ``""`` where it has none, as its fourth field, before its chip:
  ``[name, start_ns, dur_ns, scope, chip]``.  The device's events carry
  only the HLO instruction; its ``op_name`` comes from the optimized HLO
  the profiler stores per program (:func:`program_op_names`), the program
  being the ``XLA Modules`` event that the op lies in.
* :func:`reduce` returns ``trace_reduce.reduce``'s keys, computed on the
  record without the scope field, and two more: ``scope_s``, the device
  self seconds of the ops in each scope as the mean over the chips, a
  scope also summing the scopes named under it (``repro.er`` holds
  ``repro.er.gather`` and ``repro.er.scatter``), and ``span_idle_s``, the
  seconds of the traced window in which no chip runs an op, per innermost
  host span, every span.  Where a gap crosses span edges, ``span_idle_s``
  splits it there; ``idle_gaps`` puts the whole gap to the span at its
  middle and keeps the ten largest.

A record whose device ops have three fields reduces too: none has a scope;
an op without its fifth field is chip 0's.

    python3 bench/trace_scopes.py <trace dir or .xplane.pb> [--out rec.json.gz]

prints the reduction of every TPU the trace holds as one JSON object and,
with ``--out``, writes the record of the window, times rebased to its
start.
"""

from __future__ import annotations

import bisect
import collections
import pathlib
import re
import sys

if __name__ == "__main__" and __package__ is None:
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import trace_reduce as tr  # noqa: E402

SPANS = ("bench.", "repro.")
_SCOPE = re.compile(r"repro\.[A-Za-z0-9_.]*[A-Za-z0-9_]")


def scope_of(op_name: str) -> str:
    """The innermost ``repro.*`` scope in an HLO ``op_name`` path
    (``jit(f)/repro.er/vmap(repro.er.gather)/gather`` → ``repro.er.gather``),
    or ``""``."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else ""


def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field, fixed-width fields
    skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            i, v = i + (8 if wire == 1 else 4), None
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _hlo_op_names(hlo_proto) -> dict:
    """``{instruction: op_name}`` over every computation of an ``HloProto``
    (module 1 > computations 3 > instructions 2 > name 1, metadata 7 >
    op_name 2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        for g, comp in (_fields(module) if f == 1 else ()):
            for h, ins in (_fields(comp) if g == 3 else ()):
                if h != 2:
                    continue
                name = op = ""
                for k, v in _fields(ins):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op = next((_text(w) for m, w in _fields(v) if m == 2),
                                  "")
                out[name] = op
    return out


def program_op_names(xspace_path) -> dict:
    """``{program: {instruction: op_name}}`` from the ``Hlo Proto`` stats of
    the ``/host:metadata`` plane, read from the file's protobuf wire format
    (XSpace planes 1 > XPlane name 2, event metadata 4 > XEventMetadata
    name 2, stats 5 > XStat metadata id 1, bytes 6; stat metadata 5 >
    id 1, name 2), since ``jax.profiler.ProfileData`` does not expose
    them."""
    data = memoryview(pathlib.Path(xspace_path).read_bytes())
    out = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        parts = collections.defaultdict(list)
        for g, v in _fields(plane):
            parts[g].append(v)
        if not any(_text(v) == "/host:metadata" for v in parts[2]):
            continue
        stat_names = {}
        for entry in parts[5]:
            meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
            stat_names[meta.get(1)] = _text(meta.get(2, b""))
        for entry in parts[4]:
            name, proto = "", None
            for h, v in _fields(dict(_fields(entry)).get(2, b"")):
                if h == 2:
                    name = _text(v)
                elif h == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "Hlo Proto":
                        proto = stat.get(6)
            if proto is not None:
                out[name] = _hlo_op_names(proto)
    return out


def extract(xspace_path, kernels=(), chips=1) -> dict:
    """``trace_reduce.extract``'s record of one trace, with the program's
    spans among the host spans and each device op's scope as its fourth
    field (see the module docstring)."""
    record = tr.extract(xspace_path, kernels, span_prefix=SPANS, chips=chips)
    scopes = op_scopes(xspace_path, chips)
    assert len(scopes) == len(record["device"])
    for op, scope in zip(record["device"], scopes):
        op.insert(3, scope)
    return record


def op_scopes(xspace_path, chips=1) -> list:
    """The scope of each event on the ``XLA Ops`` line of each of the first
    ``chips`` TPUs, in ``trace_reduce.extract``'s order: the op's
    instruction looked up in the program whose ``XLA Modules`` event it
    lies in on that chip."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xspace_path))
    planes = tr.tpu_planes(pd, chips)
    programs = program_op_names(xspace_path) if planes else {}
    out = []
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        modules = sorted((ev.start_ns, ev.end_ns, ev.name) for ev in
                         (lines["XLA Modules"].events
                          if "XLA Modules" in lines else ()))
        starts = [m[0] for m in modules]
        for ev in lines["XLA Ops"].events:
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            names = (programs.get(modules[k][2], {})
                     if k >= 0 and ev.start_ns < modules[k][1] else {})
            instruction = ev.name.split(" = ", 1)[0].lstrip("%")
            out.append(scope_of(names.get(instruction, "")))
    return out


def _chip(op) -> int:
    return op[4] if len(op) > 4 else 0


def _base(record, kernels=()):
    """``trace_reduce.reduce`` of the record without the scope field, and
    the traced window ``(lo, hi)`` it took."""
    three = {**record, "device": [[*e[:3], _chip(e)]
                                  for e in record.get("device", [])]}
    lo, hi = tr.window(three)
    return tr.reduce(three, kernels), lo, hi


def reduce(record: dict, kernels=()) -> dict:
    """``trace_reduce.reduce``'s summary plus ``scope_s`` and
    ``span_idle_s`` (see the module docstring)."""
    chips = record.get("chips", 1)
    spans = [(n, s, s + d) for n, s, d in record.get("host", [])]
    out, lo, hi = _base(record, kernels)
    per_chip = [[] for _ in range(chips)]
    for e in record.get("device", []):
        a, b = max(e[1], lo), min(e[1] + e[2], hi)
        if b > a:
            per_chip[_chip(e)].append((e[3] if len(e) > 3 else "", a, b))
    scope_ns = collections.Counter()
    for ops in per_chip:
        for scope, t in tr._self_times(ops):
            parts = scope.split(".")
            for i in range(2, len(parts) + 1):
                scope_ns[".".join(parts[:i])] += t
    busy = tr._union([(s, e) for ops in per_chip for _, s, e in ops])
    inner = sorted(((n, s, e) for n, s, e in spans if n != tr.WINDOW_SPAN),
                   key=lambda t: t[2] - t[1])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle_ns = collections.Counter()
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        cuts = sorted({g0, g1, *(x for _, s, e in inner for x in (s, e)
                                 if g0 < x < g1)})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            cover = next((n for n, s, e in inner if s <= mid <= e),
                         tr.UNCOVERED)
            idle_ns[cover] += b - a
    per = 1e-9 / max(chips, 1)
    out["scope_s"] = {k: v * per for k, v in sorted(scope_ns.items())}
    out["span_idle_s"] = {k: v * 1e-9 for k, v in idle_ns.most_common()}
    return out


def window_record(record: dict) -> dict:
    """The events of ``record`` that overlap its traced window, times in
    whole nanoseconds from the window's start, and its ``chips``."""
    _, lo, hi = _base(record)

    def keep(events):
        return [[e[0], round(e[1] - lo), round(e[2]), *e[3:]]
                for e in events if e[1] < hi and e[1] + e[2] > lo]

    out = {"device": keep(record.get("device", [])),
           "host": keep(record.get("host", []))}
    if "chips" in record:
        out["chips"] = record["chips"]
    return out


def main(argv=None) -> int:
    import argparse
    import gzip
    import json

    from bench.harness import KERNELS

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a jax.profiler log directory or an "
                    ".xplane.pb file")
    ap.add_argument("--out", default=None,
                    help="write the window's record here (.json.gz)")
    args = ap.parse_args(argv)
    path = args.trace
    if not path.endswith(".xplane.pb"):
        path = tr.find_xspace(path)
    record = extract(path, KERNELS, chips=None)
    if args.out:
        with gzip.open(args.out, "wt") as f:
            json.dump(window_record(record), f, separators=(",", ":"))
    print(json.dumps(reduce(record, KERNELS)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
