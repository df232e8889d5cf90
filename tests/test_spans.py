"""Program spans, device scopes and compile counters (``repro.core.counters``).

Spans time eager host stages into ``counters.timings()`` (and, under a
profiler, into the trace); scopes name the apply's XLA ops by stage in
their ``op_name``; JAX's compile events land in the same table.
"""

import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import counters
from repro.core.ehyb import build_ehyb, pack_staircase
from repro.core.matrices import poisson3d27, symmetrize
from repro.core.partition import make_partition
from repro.core.spmv import EHYBDevice, ehyb_spmv_permuted


@pytest.fixture
def table():
    counters.reset()
    yield
    counters.reset()


def _calls(name):
    return counters.timings().get(name, {}).get("calls", 0)


def test_span_nesting_self_time_and_parent(table):
    with counters.span("repro.t.outer") as outer:
        with counters.span("repro.t.inner") as inner:
            sum(range(20000))
        with counters.span("repro.t.inner"):
            pass
    t = counters.timings()
    assert t["repro.t.outer"]["parent"] == ""
    assert t["repro.t.inner"]["parent"] == "repro.t.outer"
    assert t["repro.t.inner"]["calls"] == 2
    assert t["repro.t.outer"]["seconds"] == pytest.approx(outer.seconds)
    assert inner.seconds > 0
    assert t["repro.t.inner"]["self"] == pytest.approx(
        t["repro.t.inner"]["seconds"])
    assert t["repro.t.outer"]["self"] == pytest.approx(
        t["repro.t.outer"]["seconds"] - t["repro.t.inner"]["seconds"])


def test_spans_accumulate_and_reset_clears(table):
    for _ in range(3):
        with counters.span("repro.t.loop"):
            pass
    first = counters.timings()["repro.t.loop"]
    with counters.span("repro.t.loop"):
        pass
    again = counters.timings()["repro.t.loop"]
    assert (first["calls"], again["calls"]) == (3, 4)
    assert again["seconds"] >= first["seconds"]
    counters.timings()["repro.t.loop"]["calls"] = 99   # a copy
    assert counters.timings()["repro.t.loop"]["calls"] == 4
    counters.reset()
    assert "repro.t.loop" not in counters.timings()


def test_span_closes_on_an_exception(table):
    with pytest.raises(ValueError):
        with counters.span("repro.t.fails"):
            raise ValueError("x")
    with counters.span("repro.t.after"):
        pass
    t = counters.timings()
    assert t["repro.t.fails"]["calls"] == 1
    assert t["repro.t.after"]["parent"] == ""


def test_carry_nests_a_worker_thread_under_the_caller(table):
    out = {}

    def work():
        with counters.span("repro.t.worker"):
            sum(range(20000))
        out["done"] = True

    with counters.span("repro.t.caller"):
        th = threading.Thread(target=counters.carry(work))
        th.start()
        th.join(timeout=30)
    assert not th.is_alive() and out["done"]
    t = counters.timings()
    assert t["repro.t.worker"]["parent"] == "repro.t.caller"
    assert t["repro.t.caller"]["self"] == pytest.approx(
        t["repro.t.caller"]["seconds"] - t["repro.t.worker"]["seconds"])


def test_concurrent_spans_lose_no_update(table):
    import sys

    start = threading.Barrier(16)

    def work():
        start.wait(timeout=60)
        for _ in range(500):
            with counters.span("repro.t.outer"):
                with counters.span("repro.t.inner"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    t = counters.timings()
    assert t["repro.t.outer"]["calls"] == t["repro.t.inner"]["calls"] == 8000
    assert t["repro.t.outer"]["parent"] == ""
    assert t["repro.t.inner"]["parent"] == "repro.t.outer"


@pytest.fixture
def compile_counters():
    counters.start_compile_counters()


def test_a_fresh_jit_counts_one_compile_and_a_second_call_none(
        compile_counters):
    x = jnp.arange(7.0)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    compiles, lowers = _calls("jax.compile"), _calls("jax.lower")
    f(x).block_until_ready()
    assert _calls("jax.compile") == compiles + 1
    assert _calls("jax.lower") > lowers
    compiles, lowers = _calls("jax.compile"), _calls("jax.lower")
    f(x).block_until_ready()
    assert (_calls("jax.compile"), _calls("jax.lower")) == (compiles, lowers)
    assert counters.timings()["jax.compile"]["seconds"] > 0


def test_starting_the_compile_counters_twice_counts_each_compile_once(
        compile_counters):
    counters.start_compile_counters()
    x = jnp.arange(5.0)
    compiles = _calls("jax.compile")
    jax.jit(lambda v: v - 2.0)(x).block_until_ready()
    assert _calls("jax.compile") == compiles + 1


_REGISTRATION = """
import importlib, jax, jax.numpy as jnp
from repro.core import counters
def compiles(c):
    return c.timings().get("jax.compile", {}).get("calls", 0)
x = jnp.arange(3.0)
jax.jit(lambda v: v + 1.0)(x).block_until_ready()
print(compiles(counters))
counters.start_compile_counters()
c = importlib.reload(counters)
c.start_compile_counters()
jax.jit(lambda v: v + 2.0)(x).block_until_ready()
print(compiles(c))
"""


def test_importing_counters_registers_no_listener_and_a_reload_none_twice():
    import os
    import pathlib
    import subprocess
    import sys

    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [src, os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _REGISTRATION], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["0", "1"]


def _matrix():
    return symmetrize(poisson3d27(6))


def test_build_stage_times_are_the_spans(table):
    m = _matrix()
    part = make_partition(m)
    assert part.seconds == pytest.approx(
        counters.timings()["repro.partition"]["seconds"])
    counters.reset()
    e = build_ehyb(m, method="natural")
    t = counters.timings()
    pp = e.preprocess_seconds
    assert pp["partition"] == pytest.approx(t["repro.partition"]["seconds"])
    assert pp["metadata"] == pytest.approx(
        t["repro.ehyb.metadata"]["seconds"])
    assert pp["reorder"] == pytest.approx(t["repro.ehyb.reorder"]["seconds"])
    assert pp["total"] == pytest.approx(
        pp["partition"] + pp["metadata"] + pp["reorder"])
    pack_staircase(e)
    assert e.preprocess_seconds["pack"] == pytest.approx(
        counters.timings()["repro.ehyb.pack"]["seconds"])
    r = e.refill(np.asarray(m.data) * 2.0)
    assert r.preprocess_seconds["refill"] == pytest.approx(
        counters.timings()["repro.ehyb.refill"]["seconds"])
    assert r.preprocess_seconds["total"] == r.preprocess_seconds["refill"]


def test_plan_bind_and_solve_stages_nest(table):
    m = symmetrize(poisson3d27(5))
    p = api.plan(m, execution=api.ExecutionConfig(
        format="ehyb_packed", workload="solver", partition_method="natural"),
        cache=api.PlanCache())
    op = p.bind(m)
    b = jnp.ones(m.n)
    op.solve(b, tol=0.0, max_iters=3, warn=False)
    op.solve(b, tol=0.0, max_iters=3, warn=False)
    t = counters.timings()
    for name in ("repro.plan.store", "repro.plan.build"):
        assert t[name]["parent"] == "repro.plan", name
    # packing runs on the plan's worker thread, under the caller's span
    for name in ("repro.bind.key", "repro.bind.pack", "repro.bind.upload"):
        assert t[name]["parent"] == "repro.bind", name
    assert t["repro.ehyb.pack"]["parent"] == "repro.bind.pack"
    assert t["repro.solve"]["calls"] == 2
    stages = ("key", "precond", "to_space", "loop", "from_space", "finalize")
    for s in stages:
        name = f"repro.solve.{s}"
        assert t[name]["calls"] == 2 and t[name]["parent"] == "repro.solve"
    children = sum(t[f"repro.solve.{s}"]["seconds"] for s in stages)
    assert t["repro.solve"]["self"] == pytest.approx(
        t["repro.solve"]["seconds"] - children)


def test_a_solve_that_raises_closes_its_spans(table):
    from repro.reliability import SolveFailure

    m = symmetrize(poisson3d27(4))
    op = api.plan(m, cache=api.PlanCache()).bind(m)
    with pytest.raises(SolveFailure):
        op.solve(jnp.ones(m.n), tol=0.0, max_iters=2, raise_on_failure=True)
    t = counters.timings()
    assert t["repro.solve"]["calls"] == t["repro.solve.finalize"]["calls"] == 1
    with counters.span("repro.t.after"):
        pass
    assert counters.timings()["repro.t.after"]["parent"] == ""


def test_no_span_runs_per_apply(table):
    m = _matrix()
    op = api.plan(m, execution=api.ExecutionConfig(
        format="ehyb_packed", partition_method="natural"),
        cache=api.PlanCache()).bind(m)
    x = jnp.ones(m.n)
    (op @ x).block_until_ready()
    before = counters.timings()
    for _ in range(3):
        (op @ x).block_until_ready()
    assert counters.timings() == before


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def _has_scope(names, scope):
    return any(re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)", n)
               for n in names)


def test_permuted_apply_ops_carry_the_er_scopes():
    e = build_ehyb(_matrix(), method="natural")
    d = EHYBDevice.from_ehyb(e, jnp.float32)
    hlo = jax.jit(ehyb_spmv_permuted).lower(
        d, jnp.ones(e.n_pad)).compile().as_text()
    names = _op_names(hlo)
    for scope in ("repro.er", "repro.er.gather", "repro.er.scatter"):
        assert _has_scope(names, scope), scope
    assert not _has_scope(names, "repro.permute")


def test_original_space_packed_apply_ops_carry_every_scope():
    m = _matrix()
    op = api.plan(m, execution=api.ExecutionConfig(
        format="ehyb_packed", partition_method="natural"),
        cache=api.PlanCache()).bind(m)
    hlo = jax.jit(lambda o, v: op.plan._raw_apply()(o, v)).lower(
        op.obj, jnp.ones(m.n)).compile().as_text()
    names = _op_names(hlo)
    for scope in ("repro.er.window", "repro.permute"):
        assert _has_scope(names, scope), scope
    # every ER entry of the stencil sits in an ER window: no XLA ER gather
    assert not _has_scope(names, "repro.er.gather")
