"""Paper Table 1 (fp32) / Table 2 (fp64): EHYB speedup vs every baseline —
% of matrices where EHYB is faster, max/min/average speedup."""

from __future__ import annotations

import numpy as np

from .emit_util import emit_kv
from . import spmv_throughput


def summarize(rows, dtype_name):
    baselines = sorted({f for r in rows.values() for f in r} - {"ehyb"})
    out = {}
    for base in baselines:
        sp = []
        for name, fmts in rows.items():
            if base in fmts and "ehyb" in fmts:
                sp.append(fmts[base][0] / fmts["ehyb"][0])
        if not sp:
            continue
        sp = np.array(sp)
        rec = {"faster_pct": float((sp > 1).mean() * 100),
               "max": float(sp.max()), "min": float(sp.min()),
               "avg": float(sp.mean())}
        out[base] = rec
        emit_kv(f"speedup_{dtype_name}/ehyb_vs_{base}",
                f"faster={rec['faster_pct']:.0f}%;max={rec['max']:.2f};"
                f"min={rec['min']:.2f};avg={rec['avg']:.2f}")
    return out


def main():
    import jax

    rows32 = spmv_throughput.run("f32")
    t1 = summarize(rows32, "f32")
    with jax.enable_x64():
        rows64 = spmv_throughput.run("f64")
    t2 = summarize(rows64, "f64")
    # rows_f32 rides along so run.py's BENCH_spmv.json stage can reuse the
    # measured sweep instead of re-timing every format × matrix
    return {"table1_f32": t1, "table2_f64": t2, "rows_f32": rows32}


if __name__ == "__main__":
    main()
