"""CG iterations per solve, from the solver's own count
(``SolveResult.iters``)."""


def read(rec):
    return rec["iters"] / rec["ops"] if rec.get("iters") else None
