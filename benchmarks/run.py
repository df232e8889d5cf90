"""Benchmark entry point — one module per paper table/figure.

  Fig 2/3 + 4/5  -> spmv_throughput   (per-matrix GFLOP/s per format)
  framework      -> spmm_throughput   (batched k-wide apply vs k SpMVs —
                                       the megakernel amortization gate)
  Table 1/2      -> speedup_table     (EHYB vs baselines, fp32/fp64)
  Fig 6          -> preprocessing_time (partition/reorder × single-SpMV)
  §3.4           -> bytes_model       (modeled HBM bytes; int16 ablation)
  §6             -> solver_bench      (SPAI-CG amortization, original vs
                                       permuted execution space)
  framework      -> dist_halo         (sharded halo exchange vs all-gather
                                       words + distributed solve timings)
  framework      -> autotune_table    (per-matrix chosen format + bytes/nnz)
  framework      -> partition_quality (per-strategy locality/halo table +
                                       cost-priced selection gate)
  framework      -> api_overhead      (Operator API v2 dispatch vs direct
                                       engine apply; asserts < 5% overhead)
  framework      -> lm_step_bench     (smoke train/decode step times)

Prints ``name,us_per_call,derived`` CSV lines, and writes the
machine-readable perf trajectory:

  BENCH_spmv.json    — per (matrix × format): measured ns/iter, GFLOP/s,
                       rel-err, modeled HBM bytes (+ per-nnz); plus
                       ``kind: "spmm"`` records per (matrix × format × k):
                       batched-apply vs k-single-SpMV timings with
                       ``speedup_vs_k_spmv`` and the k-axis modeled bytes;
                       plus one
                       ``kind: "preprocess"`` record per matrix with
                       rebuild-vs-refill preprocessing seconds (the
                       value-refresh fast path's amortization multiplier);
                       plus ``kind: "dist"`` records per (matrix × mesh
                       size): scheduled halo words vs the all-gather words
                       the replaced dist path moved, HLO-measured
                       collective bytes for both, and distributed-vs-local
                       solve time/residual; plus ``kind: "partition"``
                       records per (matrix × partition strategy): cached
                       x-read share, ELL/ER shape, modeled solver bytes,
                       scheduled halo words at 4/8 devices, and which
                       strategy the cost model selected (gated: the
                       selection never caches fewer reads than natural);
  BENCH_solver.json  — per (matrix × format × execution space): CG seconds,
                       iters-to-converge, residual, modeled bytes/iteration
                       (the permuted-space records show the
                       2·n_pad·val_bytes perm-round-trip reduction).

Usage:
  python -m benchmarks.run                      # full module list + JSON
  python -m benchmarks.run --quick              # tiny config (CI smoke)
  python -m benchmarks.run bytes_model          # one module, CSV only
  python -m benchmarks.run --json solver_bench  # one module + JSON
  python -m benchmarks.run --json-dir out/      # JSON location
  python -m benchmarks.run --quick --verify     # + static verification of
                                                #   every built container
                                                #   (kind:"analysis" records)
  python -m benchmarks.run --calibrate          # fit + gate the calibrated
                                                #   cost model, write
                                                #   BENCH_calibration.json
                                                #   (kind:"calibration")

BENCH_*.json is written on default/--quick runs (no explicit module list) or
when --json is passed; an explicit module list alone stays CSV-only so a
quick single-table run never triggers the measured SpMV sweep.
"""

from __future__ import annotations

import argparse
import inspect
import json
import pathlib
import sys

DEFAULT_MODS = ["bytes_model", "preprocessing_time", "speedup_table",
                "spmm_throughput", "solver_bench", "dist_halo",
                "partition_quality", "autotune_table", "api_overhead",
                "lm_step_bench"]
QUICK_MODS = ["solver_bench", "preprocessing_time", "dist_halo",
              "partition_quality", "api_overhead", "spmm_throughput"]


def collect_spmm_records(results: dict, quick: bool = False) -> list:
    """kind:"spmm" batched-vs-k-SpMV records for the BENCH trajectory."""
    rows = results.get("spmm_throughput")
    if rows is None:
        from . import spmm_throughput

        rows = spmm_throughput.main(quick=quick)
    return rows


def collect_dist_records(results: dict, quick: bool = False) -> list:
    """kind:"dist" halo-vs-all-gather records for the BENCH trajectory."""
    rows = results.get("dist_halo")
    if rows is None:
        from . import dist_halo

        rows = dist_halo.main(quick=quick)
    return rows


def collect_partition_records(results: dict, quick: bool = False) -> list:
    """kind:"partition" strategy-quality records for the BENCH trajectory."""
    rows = results.get("partition_quality")
    if rows is None:
        from . import partition_quality

        rows = partition_quality.main(quick=quick)
    return rows


def collect_preprocess_records(results: dict, quick: bool = False) -> list:
    """Rebuild-vs-refill preprocessing records for the BENCH trajectory."""
    rows = results.get("preprocessing_time")
    if rows is None:
        from . import preprocessing_time

        rows = preprocessing_time.main(quick=quick)
    return [{"kind": "preprocess", "matrix": name, "n": r["n"],
             "nnz": r["nnz"], "rebuild_s": r["rebuild_s"],
             "refill_s": r["refill_s"],
             "refill_speedup_x": r["refill_speedup_x"],
             "preprocess_vs_spmv_x": r["total_x"]}
            for name, r in rows.items()]


def collect_reliability_records() -> list:
    """One kind:"reliability" record carrying the guarded-apply / solver /
    serve counters accumulated over this benchmark run — nonzero
    ``guard.*`` entries in the CI artifact mean the bench executed on a
    degraded fallback level rather than the native kernels it claims to
    time."""
    from repro.core import counters

    prefixes = ("guard.", "tune.", "solver.", "serve.")
    snap = {k: v for k, v in counters.snapshot().items()
            if k.startswith(prefixes)}
    return [{"kind": "reliability", "counters": snap}]


def collect_analysis_records(quick: bool = False) -> list:
    """kind:"analysis" records: every benchmarked container statically
    verified once, OFF the timed path (``--verify``).  One record per suite
    matrix — per-format finding counts plus the halo plan's conservation
    laws — so a corrupted build shows up in the BENCH artifact next to the
    numbers it would have poisoned."""
    from repro.analysis import verify, verify_plan
    from repro.analysis.invariants import RULES, check_halo_plan
    from repro.core import SUITE
    from repro.dist.halo import build_halo_plan

    from .common import get_ehyb, get_matrix

    from repro import autotune as at

    names = ("poisson3d_16",) if quick else tuple(SUITE)
    records = []
    for name in names:
        m = get_matrix(name)
        shared = {"ehyb": get_ehyb(name)}
        per_format = {}
        findings = []
        for fmt in at.available_formats():
            obj, _ = at.build_format(fmt, m, shared=shared)
            fs = verify(obj)
            per_format[fmt] = len(fs)
            findings += [f"{fmt}: {f}" for f in fs]
        e = shared["ehyb"]
        hs = check_halo_plan(build_halo_plan(e, 4), e)
        per_format["halo_plan"] = len(
            [f for f in hs if f.severity != "info"])
        findings += [f"halo_plan: {f}" for f in hs if f.severity != "info"]
        records.append({
            "kind": "analysis", "matrix": name, "n": m.n, "nnz": m.nnz,
            "rules_run": list(RULES), "findings_per_format": per_format,
            "findings": findings, "clean": not findings})
        print(f"verify,{name},"
              f"{'clean' if not findings else f'{len(findings)} findings'}")
    return records


def collect_calibration_records(quick: bool = False) -> list:
    """kind:"calibration" records (``--calibrate``): fit the measurement
    cost model over the suite, then gate it.

    One record per (matrix × format) sample — measured seconds next to the
    raw modeled bytes and the calibrated prediction — plus one summary
    record with the fitted coefficients and the two gates the subsystem
    promises:

    * **agreement** — over matrices where ≥2 formats were timed, the
      calibrated ranking must pick the measured-fastest format at least as
      often as raw bytes-moved does (hard assert; the fitted dispatch
      intercepts are what raw bytes cannot see);
    * **ratio band** — the geomean of calibrated-predicted / measured
      seconds must stay inside ``RATIO_BAND`` (in-sample fit, so a drift
      out of the band means the linear model stopped describing the
      machine, not that the machine got slower).

    The fitted model is persisted to the active tune store (if any), so a
    fleet pointed at the same ``REPRO_TUNE_CACHE`` ranks in calibrated
    seconds from its first plan.
    """
    from repro.tuning import calibration as cal

    RATIO_BAND = (0.2, 5.0)
    names = ("poisson3d_16", "powerlaw_4k") if quick \
        else cal.DEFAULT_SUITE
    res = cal.calibrate(names)
    model = cal.CalibrationModel.from_dict(res["model"])
    samples, ev = res["samples"], res["evaluation"]
    records = [{"kind": "calibration", "matrix": s["matrix"],
                "format": s["format"], "measured_s": s["measured_s"],
                "modeled_bytes": s["modeled_bytes"],
                "hlo_bytes": s["hlo_bytes"],
                "calibrated_s": model.predict(s["terms"], s["format"])}
               for s in samples]
    summary = {"kind": "calibration", "matrix": None, "format": None,
               "backend": model.backend, "coef": model.coef,
               "intercept": model.intercept,
               "fingerprint": model.fingerprint(),
               "persisted": bool(res.get("persisted")), **ev}
    records.append(summary)
    print(f"calibration,agree_calibrated,{ev['agree_calibrated']}"
          f"/{ev['contested']}")
    print(f"calibration,agree_raw,{ev['agree_raw']}/{ev['contested']}")
    print(f"calibration,ratio_geomean,{ev['ratio_geomean']:.3f}")
    assert ev["agree_calibrated"] >= ev["agree_raw"], (
        f"calibrated ranking ({ev['agree_calibrated']}/{ev['contested']}) "
        f"lost to raw bytes ({ev['agree_raw']}/{ev['contested']})")
    assert RATIO_BAND[0] <= ev["ratio_geomean"] <= RATIO_BAND[1], (
        f"modeled-vs-measured geomean {ev['ratio_geomean']:.3f} outside "
        f"{RATIO_BAND}")
    return records


def collect_spmv_records(quick: bool = False, rows=None) -> list:
    """Measured SpMV timings joined with the modeled-bytes table.

    ``rows`` (from a speedup_table/spmv_throughput run earlier in the same
    invocation) skips re-timing the whole suite."""
    from repro import autotune as at

    from . import spmv_throughput
    from .common import get_ehyb, get_matrix

    if rows is None:
        suite = ("poisson3d_16",) if quick else None
        rows = spmv_throughput.run("f32", suite=suite)
    records = []
    for name, fmts in rows.items():
        m = get_matrix(name)
        table = at.model_table(m, 4, shared={"ehyb": get_ehyb(name)})
        for fmt, (t, gflops, err) in fmts.items():
            records.append({
                "matrix": name, "n": m.n, "nnz": m.nnz, "format": fmt,
                "dtype": "f32", "ns_per_iter": t * 1e9, "gflops": gflops,
                "relerr": err, "modeled_bytes": table[fmt],
                "modeled_bytes_per_nnz": table[fmt] / max(m.nnz, 1)})
    return records


def _run_module(name: str, quick: bool):
    import importlib

    mod = importlib.import_module(f"benchmarks.{name}")
    print(f"# === {name} ===")
    if "quick" in inspect.signature(mod.main).parameters:
        return mod.main(quick=quick)
    return mod.main()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("modules", nargs="*", help="benchmark modules to run")
    ap.add_argument("--quick", action="store_true",
                    help="tiny matrix config (CI smoke)")
    ap.add_argument("--json-dir", default=None,
                    help="where to write BENCH_*.json (default: repo root "
                         "for full runs; bench-out/ for --quick, so a tiny "
                         "config never overwrites the committed full-suite "
                         "trajectory)")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_*.json even with an explicit module list")
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing BENCH_*.json")
    ap.add_argument("--verify", action="store_true",
                    help="statically verify every built container once, "
                         "off the timed path, and emit kind:\"analysis\" "
                         "records into BENCH_spmv.json")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit the measurement cost model over the suite, "
                         "gate agreement + modeled-vs-measured ratio, and "
                         "emit kind:\"calibration\" records into "
                         "BENCH_spmv.json (persists to REPRO_TUNE_CACHE "
                         "when set)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.calibrate and not args.modules:
        # calibration is its own measured pass — don't drag the full
        # benchmark module list along unless explicitly asked for
        print("# === calibrate ===")
        cal_records = collect_calibration_records(args.quick)
        if not args.no_json:
            out = pathlib.Path(args.json_dir or "bench-out")
            out.mkdir(parents=True, exist_ok=True)
            path = out / "BENCH_calibration.json"
            path.write_text(json.dumps(cal_records, indent=1,
                                       sort_keys=True) + "\n")
            print(f"wrote {path} ({len(cal_records)} records)")
        return

    mods = args.modules or (QUICK_MODS if args.quick else DEFAULT_MODS)
    results = {name: _run_module(name, args.quick) for name in mods}

    if args.no_json or (args.modules and not args.json):
        if args.verify:
            print("# === verify ===")
            collect_analysis_records(args.quick)
        if args.calibrate:
            print("# === calibrate ===")
            collect_calibration_records(args.quick)
        return
    if args.json_dir is None:
        root = pathlib.Path(__file__).parent.parent
        out_dir = root / "bench-out" if args.quick else root
    else:
        out_dir = pathlib.Path(args.json_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    print("# === BENCH json ===")
    rows = (results.get("speedup_table") or {}).get("rows_f32") \
        or results.get("spmv_throughput", {}).get("f32")
    spmv_records = collect_spmv_records(args.quick, rows=rows)
    spmv_records += collect_spmm_records(results, args.quick)
    spmv_records += collect_preprocess_records(results, args.quick)
    spmv_records += collect_dist_records(results, args.quick)
    spmv_records += collect_partition_records(results, args.quick)
    spmv_records += results.get("api_overhead") or []
    if args.verify:
        print("# === verify ===")
        spmv_records += collect_analysis_records(args.quick)
    if args.calibrate:
        print("# === calibrate ===")
        spmv_records += collect_calibration_records(args.quick)
    spmv_records += collect_reliability_records()
    solver_records = results.get("solver_bench")
    if solver_records is None:
        from . import solver_bench

        solver_records = solver_bench.main(quick=args.quick)
    for fname, payload in (("BENCH_spmv.json", spmv_records),
                           ("BENCH_solver.json", solver_records)):
        path = out_dir / fname
        path.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
        print(f"wrote {path} ({len(payload)} records)")


if __name__ == '__main__':
    main()
