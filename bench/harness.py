"""One cell of the benchmark: set up, measure a closed-loop window, check.

:func:`load_cell` finds everything a cell is made of by the names in
``BENCHMARK.json``.  :func:`run` then

1. generates the configuration's matrix (the operator belongs to the
   configuration, not to the seed);
2. runs ``repro.api.plan`` -> ``Plan.bind`` with the configuration's
   format and value dtype and the traffic's workload context (the
   partition is the plan's own choice, warm-started from the tune store
   under ``bench/.tune_store``); a cell on more than one chip plans over
   a one-axis mesh of its chips, in the ``"dist"`` context;
3. draws the pool of right-hand sides from ``--seed`` on the device, puts
   it whole on every chip of the mesh where there is one, and warms the
   timed entry up on the first of them;
4. drives the timed entry in a closed loop for ``seconds`` (one caller,
   each call ended by ``block_until_ready``; the call in flight at the
   deadline is finished and counted), optionally under the profiler;
5. reads the device's peak memory, then checks a seed-drawn sample of the
   window's outputs against the float64 reference, with the guard
   counters, the plan's degradation record and, on a TPU, the Pallas
   kernel in the compiled apply (for a sharded plan, the ``shard_map``
   program its applies and solves run).

It returns the result object that ``run.py`` prints as its last line.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import pathlib
import random
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
KERNELS = ("ehyb_packed_spmv", "fused_cg_update")


class BenchError(Exception):
    """The cell cannot be run as specified."""


def _json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    path = pathlib.Path(path)
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    name = "bench._by_path." + path.relative_to(BENCH).as_posix()[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(name: str, benchmark=None) -> dict:
    """The cell's entry with its configuration, traffic and limits loaded,
    and the metrics it reports: an end-to-end metric applies where its
    ``workloads`` lists the cell or where it has none; a per-layer metric
    where its ``workloads`` lists the cell or, without the key, where the
    cell reports the end-to-end metric it ``moves``."""
    bm = benchmark or _json(ROOT / "BENCHMARK.json")
    entry = _named(bm["workloads"], name, "workload")
    cfg_entry = _named(bm["configs"], entry["config"], "config")
    e2e = [m for m in bm["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in names
                              else [])]
    return {"name": name, "chips": entry["chips"],
            "config": _json(ROOT / cfg_entry["file"]),
            "traffic": _json(BENCH / "traffic" / f"{entry['traffic']}.json"),
            "cell": _json(BENCH / "workloads" / f"{name}.json"),
            "end_to_end": e2e, "per_layer": layer}


def generator(cfg: dict):
    return load_module(BENCH / "gen" / f"{cfg['generator']}.py")


def driver(traffic: dict):
    return load_module(BENCH / "traffic" / f"{traffic['kind']}.py")


def reader(metric: str):
    return load_module(BENCH / "metrics" / f"{metric}.py").read


def make_pool(n: int, k: int, seed: int, count: int):
    """``count`` standard-normal float32 vectors (``(n, k)`` for ``k > 1``)
    drawn on the device from ``seed``; every bit of a 64-bit seed counts."""
    import jax
    import jax.numpy as jnp

    seed = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    shape = (n,) if k == 1 else (n, k)
    pool = [jax.random.normal(jax.random.fold_in(key, j), shape, jnp.float32)
            for j in range(count)]
    return jax.block_until_ready(pool)


def cell_mesh(chips: int):
    """None on one chip; else a one-axis mesh (``"data"``) over the first
    ``chips`` devices."""
    if chips == 1:
        return None
    from repro.compat import make_mesh

    return make_mesh((chips,), ("data",))


def place(pool, mesh):
    """The pool where a sharded operator's original-space entry takes its
    vectors: whole on every chip of the mesh (the entry gathers them into
    the permuted space with a permutation held on every chip), so that no
    call of the window moves them.  Unchanged without a mesh."""
    if mesh is None:
        return pool
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    whole = NamedSharding(mesh, PartitionSpec())
    return jax.block_until_ready([jax.device_put(v, whole) for v in pool])


def _guard_counts() -> dict:
    from repro.core import counters

    return {k: v for k, v in counters.snapshot().items()
            if k.startswith("guard.")}


def _kernel_missing(op, pool) -> int:
    """1 when the compiled permuted apply holds no Pallas kernel
    (``tpu_custom_call``), else 0; only asked on a TPU.  For a sharded
    plan, the program asked is the ``shard_map``-ed original-space apply,
    whose per-chip body (local tiles, halo exchange, ER) is the one the
    sharded solve runs in its loop."""
    import jax

    if op.plan.is_sharded:
        fn, x = op.raw_apply, pool[0]
    else:
        fn, x = op.raw_apply_permuted, op.to_space(pool[0])
    hlo = jax.jit(lambda o, v: fn(o, v)).lower(op.obj, x).compile().as_text()
    return 0 if "tpu_custom_call" in hlo else 1


def _cache_entries() -> int:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT /
                                                              ".jax_cache")
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


def _window(op, pool, drv, traffic, seconds, rng, sample):
    """The closed loop over the pool, in pool order.  Returns per-call
    seconds, infos, the window's seconds and a uniform reservoir sample of
    ``(pool index, output)``.  Python's cyclic collector is kept out of the
    window: everything set-up made is frozen out of its reach first."""
    import jax

    times, infos, kept = [], [], []
    first = None
    i = 0
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                j = i % len(pool)
                t, t1, out, info = _timed_call(op, pool[j], drv, traffic)
                if first is None:
                    first = t
                times.append(t1 - t)
                infos.append(info)
                if len(kept) < sample:
                    kept.append((j, out))
                else:
                    r = rng.randrange(i + 1)
                    if r < sample:
                        kept[r] = (j, out)
                i += 1
                if t1 - first >= seconds:
                    break
    finally:
        gc.enable()
        gc.unfreeze()
    return times, infos, t1 - first, kept


def _timed_call(op, item, drv, traffic):
    """One call of the timed entry, ended by ``block_until_ready``."""
    import jax

    with jax.profiler.TraceAnnotation(drv.SPAN):
        t = time.perf_counter()
        out, info = drv.call(op, item, traffic)
        jax.block_until_ready(out)
        return t, time.perf_counter(), out, info


def run(spec: dict, seeds, seconds: float, trace: bool, *, t0: float,
        values=None, log=print, store=BENCH / ".tune_store"):
    """Run the cell on each seed after one set-up; yields one result
    object per seed.  ``values`` binds the plan's values in another dtype
    than the configuration's (the lower-precision control, on the same
    plan); ``store`` is the tune store's directory."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.autotune import get_format
    from repro.core.matrices import SparseCSR
    from repro.tuning.store import set_store

    from bench import reference, trace_reduce

    cfg, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    chips = int(spec["chips"])
    dtype = jnp.dtype(values or cfg["dtype"])
    k = int(traffic.get("k", 1))
    drv = driver(traffic)
    mesh = cell_mesh(chips)
    cache_before = _cache_entries()
    guard_before = _guard_counts()

    g = generator(cfg).generate(cfg)
    for a in ("indptr", "indices", "data"):
        g[a].setflags(write=False)   # the reference reads them after the run
    m = SparseCSR(n=g["n"], indptr=g["indptr"], indices=g["indices"],
                  data=g["data"])
    log(f"matrix: {cfg['name']} n={m.n} nnz={m.nnz} "
        f"gen_s={time.perf_counter() - t0:.3f}")
    set_store(str(store))
    t = time.perf_counter()
    # "dist" is the only solver-loop context that a plan over a mesh of
    # more than one device accepts (the traffic's own is refused there)
    workload, on_mesh = ((traffic["workload"], {}) if mesh is None
                         else ("dist", {"mesh": mesh}))
    p = api.plan(m, **on_mesh, execution=api.ExecutionConfig(
        format=cfg["format"], workload=workload,
        dtype=jnp.dtype(cfg["dtype"])))
    plan_s = time.perf_counter() - t
    t = time.perf_counter()
    op = p.bind(m, dtype=dtype)
    jax.block_until_ready(op.obj)
    bind_s = time.perf_counter() - t
    from repro.core import counters

    snap = counters.snapshot()
    log(f"plan: format={p.format} partition={p.partition_strategy} "
        f"chips={chips} sharded={p.is_sharded} "
        f"plan_s={plan_s:.3f} bind_s={bind_s:.3f} tune_store hit="
        f"{snap.get('tune_store.hit', 0)} miss="
        f"{snap.get('tune_store.miss', 0)}")
    dev = jax.devices()
    size = {"n": m.n, "nnz": m.nnz, "k": k, "dtype": dtype.name}
    kernel_missing = None
    setup_s = None
    a64 = None
    for seed in seeds:
        pool = place(make_pool(m.n, k, seed, int(traffic["pool"])), mesh)
        if setup_s is None:
            t = time.perf_counter()
            jax.block_until_ready(drv.call(op, pool[0], traffic)[0])
            log(f"warm-up: first call {time.perf_counter() - t:.3f} s")
            setup_s = time.perf_counter() - t0
        tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            jax.profiler.start_trace(tdir)
        try:
            times, infos, window_s, kept = _window(
                op, pool, drv, traffic, seconds, random.Random(seed),
                int(cell["sample"]))
        finally:
            if trace:
                jax.profiler.stop_trace()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in dev)
        summary = None
        if trace:
            record = trace_reduce.extract(trace_reduce.find_xspace(tdir),
                                          KERNELS, chips=chips)
            shutil.rmtree(tdir, ignore_errors=True)
            summary = trace_reduce.reduce(record, KERNELS)
        iters, failed = drv.tally(infos, traffic)
        log(f"window: seed={seed} calls={len(times)} window_s={window_s:.3f} "
            f"iters={iters} failed={failed}")

        with jax.profiler.TraceAnnotation("bench.check"):
            if kernel_missing is None:
                kernel_missing = (_kernel_missing(op, pool)
                                  if dev[0].platform == "tpu"
                                  and get_format(p.format).kernel == "pallas"
                                  else 0)
            hosted = {}
            pairs = []
            for j, out in kept:
                if j not in hosted:
                    hosted[j] = np.asarray(pool[j])
                pairs.append((hosted[j], np.asarray(out)))
            del kept, pool
            if a64 is None:
                a64 = reference.csr64(g)
            numbers = dict(drv.checks(a64, pairs, traffic))
        limits = dict(cell["limits"])
        moves = sum(v - guard_before.get(c, 0)
                    for c, v in _guard_counts().items())
        numbers.update({"guard_moves": moves,
                        "degraded": len(p.degraded),
                        "kernel_missing": kernel_missing,
                        "failed": failed})
        limits.update({"guard_moves": 0, "degraded": 0,
                       "kernel_missing": 0, "failed": 0})
        correct = bool(times) and all(numbers[n] <= limits[n]
                                      for n in limits)

        if trace:
            rec = {"trace": summary, "plan_s": plan_s, "bind_s": bind_s,
                   "ops": len(times), "iters": iters, "chips": chips,
                   "device_kind": dev[0].device_kind, **size}
            metrics = {}
            for mt in spec["per_layer"]:
                v = reader(mt["name"])(rec)
                if v is not None:
                    metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
        else:
            e2e = drv.end_to_end(window_s, times, infos, size)
            e2e["setup_s"] = setup_s
            metrics = {mt["name"]: {"value": e2e[mt["name"]],
                                    "unit": mt["unit"]}
                       for mt in spec["end_to_end"]}
        device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
                  "count": len(dev), "memory_peak_bytes": int(peak)}
        result = {"correct": correct, "attempted": len(times),
                  "failed": failed, "metrics": metrics, "device": device}
        if trace:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            device["busy_s_by_chip"] = summary["busy_s_by_chip"]
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
        result["checks"] = {n: {"value": numbers[n], "limit": limits[n]}
                            for n in limits}
        log(f"compile cache: {cache_before} entries before, "
            f"{_cache_entries()} after")
        yield result


def control(spec: dict, seeds, values: str, log=print):
    """The control that puts the plain reference in the program's place,
    computed in ``values``: the matrix and every vector it stores rounded to
    that precision, the sums in float64.  Yields, per seed, the cell's
    numbers on every input of the pool, each beside its limit."""
    import numpy as np

    from bench import reference

    cfg, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    drv = driver(traffic)
    g = generator(cfg).generate(cfg)
    a64 = reference.csr64(g)
    rnd = reference.rounder(values)
    low = a64.copy()
    low.data = rnd(low.data)
    for seed in seeds:
        pool = make_pool(g["n"], int(traffic.get("k", 1)), seed,
                         int(traffic["pool"]))
        pairs = []
        for v in pool:
            v = np.asarray(v, np.float64)
            pairs.append((v, drv.reference(low, v, traffic, rnd)))
        numbers = drv.checks(a64, pairs, traffic)
        log(f"control: seed={seed} values={values} inputs={len(pairs)}")
        limits = cell["limits"]
        yield {"correct": all(numbers[n] <= limits[n] for n in limits),
               "checks": {n: {"value": numbers[n], "limit": limits[n]}
                          for n in limits}}


def print_checks(result, file=sys.stderr) -> None:
    """Each number compared, beside its limit, one per line."""
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=file, flush=True)
