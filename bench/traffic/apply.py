"""Closed-loop SpMVs (SpMMs for ``k > 1``): ``LinearOperator @ x`` in the
original space, one ``x`` at a time."""

from __future__ import annotations

import statistics

from bench import reference as ref, work

SPAN = "bench.apply"


def call(op, x, traffic):
    return op @ x, None


def reference(a, x, traffic, rnd):
    """The reference's apply in the program's place (the control)."""
    return rnd(a @ rnd(x))


def tally(infos, traffic):
    return 0, 0


def checks(a64, pairs, traffic) -> dict:
    """The worst max-norm relative error of the applies against float64."""
    return {"apply_err": max(ref.apply_error(a64, x, y)
                             for x, y in pairs)}


def end_to_end(window_s, times, infos, size) -> dict:
    flops = work.spmv_flops(size["nnz"], size["k"]) * len(times)
    p95 = (statistics.quantiles(times, n=20, method="inclusive")[18]
           if len(times) > 1 else times[0])
    return {"apply_gflops": flops / window_s * 1e-9,
            "apply_ms_p95": p95 * 1e3}
