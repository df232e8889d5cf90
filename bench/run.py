#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip and print its result line.

    python3 bench/run.py --workload hpcg.cg --seed 7 --seconds 10 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit, also printed as the last
lines of standard error.  It exits non-zero and prints no result when JAX
finds no TPU, or fewer chips than the cell asks for.

Options for the maintainer, never used by the benchmark's own runs:
``--seed 1,2,3`` runs one set-up and then a window per seed (one result
line each); ``--values bfloat16`` binds the values in a lower precision
than the configuration states (the program's own lower-precision path);
``--control bfloat16`` puts the plain reference, computed in that
precision, in the program's place.  Each cell's ``control`` in
``bench/workloads/<cell>.json`` names the one the checks must fail.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _seeds(text: str):
    return [int(s) for s in text.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=_seeds, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--values", default=None)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from bench import harness
        spec = harness.load_cell(args.workload)
        from repro.compile_cache import enable_compile_cache
    except (ImportError, OSError, KeyError, harness.BenchError) as e:
        print(f"bench: cannot load {args.workload!r}: {e}", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < spec["chips"]:
        print(f"bench: needs {spec['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    os.chdir(ROOT)

    def log(msg):
        print(f"[{time.perf_counter() - T0:8.3f}] {msg}", file=sys.stderr,
              flush=True)

    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {enable_compile_cache()}")
    results = (harness.control(spec, args.seed, args.control, log=log)
               if args.control else
               harness.run(spec, args.seed, args.seconds, bool(args.trace),
                           t0=T0, values=args.values, log=log))
    for result in results:
        harness.print_checks(result)
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
