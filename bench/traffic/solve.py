"""Closed-loop linear solves: ``LinearOperator.solve`` on one ``b`` at a
time, from ``x0 = 0`` for a fixed number of iterations (tolerance 0), as
HPCG runs its CG sets: every solve does the same work."""

from __future__ import annotations

from bench import reference as ref

SPAN = "bench.solve"


def call(op, b, traffic):
    r = op.solve(b, method=traffic["method"], precond=traffic["precond"],
                 tol=0.0, max_iters=int(traffic["iterations"]), warn=False)
    return r.x, r.iters


def reference(a, b, traffic, rnd):
    """The reference's solve in the program's place (the control)."""
    return ref.cg(a, b, int(traffic["iterations"]), rnd)


def tally(infos, traffic):
    """``(iterations summed over the solves, solves that stopped short)``."""
    iters = [int(i) for i in infos]
    return sum(iters), sum(i != int(traffic["iterations"]) for i in iters)


def checks(a64, pairs, traffic) -> dict:
    """The worst relative error of a solve's ``x`` against the float64
    reference's after the same iterations, one reference per ``b``."""
    refs = {}
    worst = 0.0
    for b, x in pairs:
        if id(b) not in refs:
            refs[id(b)] = ref.cg(a64, b, int(traffic["iterations"]))
        worst = max(worst, ref.relative_error(x, refs[id(b)]))
    return {"x_err": worst}


def end_to_end(window_s, times, infos, size) -> dict:
    return {"solve_ms": window_s * 1e3 / len(times)}
