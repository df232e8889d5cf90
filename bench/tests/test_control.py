"""The checks fail what they must: the lower-precision control and the
faults a cell can have, planted under the harness at a small size."""

import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.cells import CELLS, run_small, small_spec


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_values_are_not_correct(cell, tmp_path):
    """The cell's control, one step below the configuration's float32,
    fails the cell's limit: the program's own bfloat16 value path, or,
    where that path changes nothing (HPCG's 26 and -1 are exact in
    bfloat16), the plain reference computed in bfloat16 in its place."""
    ctl = small_spec(cell)["cell"]["control"]
    if ctl["kind"] == "program":
        (r,) = run_small(cell, tmp_path, values=ctl["values"])
    else:
        (r,) = harness.control(small_spec(cell), [3], ctl["values"],
                               log=lambda msg: None)
    assert not r["correct"]
    failing = [n for n, c in r["checks"].items() if c["value"] > c["limit"]]
    assert failing and set(failing) <= {"x_err", "apply_err"}, failing


def test_bfloat16_values_change_nothing_in_hpcg(tmp_path):
    """Why ``hpcg.cg`` needs the reference as its control: the program's
    bfloat16 value path solves exactly as its float32 one."""
    (lo,) = run_small("hpcg.cg", tmp_path, values="bfloat16")
    (hi,) = run_small("hpcg.cg", tmp_path)
    assert lo["checks"]["x_err"] == hi["checks"]["x_err"]


def _alter_first_entry(out):
    return out.at[0].add(jnp.asarray(1.0, out.dtype))


def test_altered_apply_answer_is_not_correct(tmp_path, monkeypatch):
    from repro.api.operator import LinearOperator

    matmul = LinearOperator.__matmul__
    monkeypatch.setattr(LinearOperator, "__matmul__",
                        lambda op, x: _alter_first_entry(matmul(op, x)))
    (r,) = run_small("elast68.spmv", tmp_path)
    assert not r["correct"]
    assert r["checks"]["apply_err"]["value"] > r["checks"]["apply_err"][
        "limit"]


@pytest.mark.parametrize("fault", ["altered", "unchanged", "short"])
def test_broken_solve_is_not_correct(fault, tmp_path, monkeypatch):
    """A solve whose answer is altered where it is produced, that returns
    its starting state (x0 = 0) unchanged, or that stops an iteration short
    of the set."""
    from repro.api.operator import LinearOperator

    solve = LinearOperator.solve

    def broken(op, b, **kw):
        if fault == "short":
            return solve(op, b, **{**kw, "max_iters": kw["max_iters"] - 1})
        r = solve(op, b, **kw)
        x = (_alter_first_entry(r.x) if fault == "altered"
             else jnp.zeros_like(r.x))
        return r._replace(x=x) if hasattr(r, "_replace") else type(r)(
            x=x, iters=r.iters, residual=r.residual,
            converged=r.converged, status_code=r.status_code)

    monkeypatch.setattr(LinearOperator, "solve", broken)
    (r,) = run_small("hpcg.cg", tmp_path)
    assert not r["correct"]
    if fault == "short":
        assert r["failed"] == r["attempted"] and r["checks"]["failed"][
            "value"] > 0
    else:
        assert r["checks"]["x_err"]["value"] > r["checks"]["x_err"][
            "limit"]
