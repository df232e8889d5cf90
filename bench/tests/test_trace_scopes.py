"""The program's spans and scopes in a trace (``bench/trace_scopes.py``),
and the readers of the program's own table (``bench/metrics/program.py``)."""

import gzip
import json
import pathlib

import pytest

from bench import harness, trace_reduce, trace_scopes

DATA = pathlib.Path(__file__).resolve().parent / "data"
KERNELS = harness.KERNELS

# Nanoseconds.  Window [0, 100).  One solve: its key and precond stages
# leave the device idle, the loop runs the kernel, two ER ops and an op
# with no scope, nested in a while op; a permutation gather after it.
SYNTHETIC = {
    "device": [
        ["while.1", 30.0, 40.0, ""],
        ["ehyb_packed_spmv", 30.0, 5.0, ""],
        ["fusion.10", 35.0, 20.0, "repro.er.gather"],
        ["scatter.2", 55.0, 5.0, "repro.er.scatter"],
        ["broadcast.3", 60.0, 2.0, "repro.er"],
        ["fused_cg_update", 62.0, 8.0, ""],
        ["gather.4", 80.0, 10.0, "repro.permute"],
    ],
    "host": [
        ["bench.window", 0.0, 100.0],
        ["bench.solve", 0.0, 95.0],
        ["repro.solve", 5.0, 85.0],
        ["repro.solve.key", 5.0, 15.0],
        ["repro.solve.precond", 20.0, 8.0],
        ["repro.solve.finalize", 70.0, 20.0],
    ],
}


def test_scopes_sum_into_their_parents():
    s = trace_scopes.reduce(SYNTHETIC, KERNELS)
    assert s["scope_s"] == {
        "repro.er": pytest.approx(27e-9),
        "repro.er.gather": pytest.approx(20e-9),
        "repro.er.scatter": pytest.approx(5e-9),
        "repro.permute": pytest.approx(10e-9)}


def test_every_idle_gap_goes_to_its_innermost_span():
    s = trace_scopes.reduce(SYNTHETIC, KERNELS)
    # idle [0, 30): [0, 5) bench.solve, [5, 20) key, [20, 28) precond,
    # [28, 30) repro.solve; [70, 80) finalize; [90, 100): [90, 95)
    # bench.solve, [95, 100) no span
    assert s["span_idle_s"] == {"repro.solve.key": pytest.approx(15e-9),
                                "repro.solve.finalize": pytest.approx(10e-9),
                                "bench.solve": pytest.approx(10e-9),
                                "repro.solve.precond": pytest.approx(8e-9),
                                "host.loop": pytest.approx(5e-9),
                                "repro.solve": pytest.approx(2e-9)}
    assert sum(s["span_idle_s"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    # idle_gaps puts each whole gap to the span at its middle
    assert dict(s["idle_gaps"]) == {"repro.solve.key": pytest.approx(30e-9),
                                    "repro.solve.finalize": pytest.approx(
                                        10e-9),
                                    "bench.solve": pytest.approx(10e-9)}


def test_the_existing_keys_are_trace_reduce_s():
    s = trace_scopes.reduce(SYNTHETIC, KERNELS)
    three = {"device": [e[:3] for e in SYNTHETIC["device"]],
             "host": SYNTHETIC["host"]}
    base = trace_reduce.reduce(three, KERNELS)
    assert set(s) == set(base) | {"scope_s", "span_idle_s"}
    assert {k: s[k] for k in base} == base


def test_a_record_without_a_window_span_reduces_over_its_device_ops():
    rec = {"device": [e for e in SYNTHETIC["device"] if e[0] != "while.1"],
           "host": [h for h in SYNTHETIC["host"] if h[0] != "bench.window"]}
    s = trace_scopes.reduce(rec, KERNELS)
    # window [30, 90): idle [70, 80) in finalize only
    assert s["window_s"] == pytest.approx(60e-9)
    assert s["span_idle_s"] == {"repro.solve.finalize": pytest.approx(10e-9)}
    assert s["scope_s"]["repro.permute"] == pytest.approx(10e-9)
    assert trace_scopes.window_record(rec)["device"][0] == [
        "ehyb_packed_spmv", 0, 5, ""]


def _load(name):
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


def test_a_record_without_scopes_reduces_as_before():
    """The recorded ``hpcg.cg`` window of the first chip benchmark: three
    fields per device op, no program spans."""
    rec = _load("hpcg_cg_trace.json.gz")
    s = trace_scopes.reduce(rec, KERNELS)
    base = trace_reduce.reduce(rec, KERNELS)
    assert {k: s[k] for k in base} == base
    assert s["window_s"] == pytest.approx(0.90002924)
    assert s["busy_s"] == pytest.approx(0.449087275)
    assert s["kernel_s"]["ehyb_packed_spmv"] == pytest.approx(0.002595632)
    assert s["scope_s"] == {}
    # split at span edges: 29 us of the gap between two solves lies
    # outside both bench.solve spans
    idle = s["span_idle_s"]
    assert set(idle) == {"bench.solve", "host.loop"}
    assert idle["host.loop"] == pytest.approx(2.924e-05)
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def _recorded_chip():
    """A traced ``hpcg.cg`` path on a TPU v5 lite at a 16³ grid: three
    solves of 10 Jacobi-CG iterations, then three original-space applies,
    in one ``bench.window``; written by ``trace_scopes.py --out`` from
    ``hpcg16.xplane.pb.gz``."""
    return trace_scopes.reduce(_load("hpcg16_trace.json.gz"), KERNELS)


def test_the_record_is_what_extract_reads_from_the_chip_trace(tmp_path):
    """The TPU's op events carry no ``op_name``: each op's scope comes from
    the program's HLO in the trace's metadata plane, found through the
    ``XLA Modules`` event the op lies in."""
    import shutil

    xspace = tmp_path / "hpcg16.xplane.pb"
    with gzip.open(DATA / "hpcg16.xplane.pb.gz", "rb") as f, \
            open(xspace, "wb") as out:
        shutil.copyfileobj(f, out)
    rec = trace_scopes.extract(xspace, KERNELS)
    assert trace_scopes.window_record(rec) == _load("hpcg16_trace.json.gz")


def test_recorded_chip_trace_splits_the_xla_side_by_scope():
    s = _recorded_chip()
    scopes = s["scope_s"]
    assert s["kernel_s"]["ehyb_packed_spmv"] > 0
    assert scopes["repro.er.gather"] > 0 and scopes["repro.er.scatter"] > 0
    assert scopes["repro.er"] == pytest.approx(
        scopes["repro.er.gather"] + scopes["repro.er.scatter"])
    assert scopes["repro.permute"] > 0       # the applies' gathers
    assert scopes["repro.er"] + scopes["repro.permute"] <= s["other_s"]


def test_recorded_chip_trace_puts_idle_to_the_solve_stages():
    s = _recorded_chip()
    assert s["kernel_s"]["fused_cg_update"] > 0
    idle = s["span_idle_s"]
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    stages = {k: v for k, v in idle.items() if k.startswith("repro.solve.")}
    assert {"repro.solve.key", "repro.solve.precond", "repro.solve.to_space",
            "repro.solve.from_space", "repro.solve.finalize"} <= set(stages)
    # the stages name the solves' idle time; the solve span itself and
    # the harness's span hold little of it
    solve_idle = sum(stages.values()) + idle.get("repro.solve", 0.0)
    assert idle.get("repro.solve", 0.0) < 0.1 * solve_idle
    assert idle.get("bench.solve", 0.0) < 0.1 * solve_idle
    assert dict(s["idle_gaps"])["repro.solve.to_space"] > 0


@pytest.mark.parametrize("op_name,scope", [
    ("jit(cg)/while/body/jit(ehyb_spmv_packed_pallas_permuted)/repro.er/"
     "vmap(repro.er.gather)/ew,ewr->er/dot_general", "repro.er.gather"),
    ("jit(f)/repro.er/vmap(repro.er.scatter)/scatter-add", "repro.er.scatter"),
    ("jit(f)/repro.er/broadcast_in_dim", "repro.er"),
    ("jit(ehyb_spmv_packed_pallas)/repro.permute/gather", "repro.permute"),
    ("jit(cg)/while/body/add", ""),
    ("", ""),
])
def test_scope_of(op_name, scope):
    assert trace_scopes.scope_of(op_name) == scope


def test_window_record_keeps_the_window_rebased():
    rec = {"device": [["a", 90.0, 20.0, "repro.er"], ["b", 150.0, 5.0, ""],
                      ["c", 300.0, 5.0, ""]],
           "host": [["bench.window", 100.0, 100.0],
                    ["repro.solve", 40.0, 30.0]]}
    w = trace_scopes.window_record(rec)
    assert w == {"device": [["a", -10, 20, "repro.er"], ["b", 50, 5, ""]],
                 "host": [["bench.window", 0, 100]]}


def test_a_span_lands_in_the_trace_with_its_name(tmp_path):
    import jax

    from repro.core import counters

    jax.profiler.start_trace(str(tmp_path))
    try:
        with counters.span("repro.t.outer", id=7):
            with counters.span("repro.t.inner"):
                jax.numpy.arange(5.0).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    rec = trace_scopes.extract(trace_reduce.find_xspace(tmp_path), KERNELS)
    names = [h[0] for h in rec["host"]]
    assert "repro.t.outer" in names and "repro.t.inner" in names
    (outer,) = [h for h in rec["host"] if h[0] == "repro.t.outer"]
    (inner,) = [h for h in rec["host"] if h[0] == "repro.t.inner"]
    assert outer[1] <= inner[1]
    assert inner[1] + inner[2] <= outer[1] + outer[2]


def test_program_op_names_read_the_profiled_hlo(tmp_path):
    """The profiler keeps each program's optimized HLO; its ops' ``op_name``
    carries the program's scopes."""
    import jax
    import jax.numpy as jnp

    from repro.core.ehyb import build_ehyb
    from repro.core.matrices import poisson3d27, symmetrize
    from repro.core.spmv import EHYBDevice, ehyb_spmv_permuted

    e = build_ehyb(symmetrize(poisson3d27(5)), method="natural")
    d = EHYBDevice.from_ehyb(e, jnp.float32)
    x = jnp.ones(e.n_pad)
    ehyb_spmv_permuted(d, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        ehyb_spmv_permuted(d, x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    programs = trace_scopes.program_op_names(
        trace_reduce.find_xspace(tmp_path))
    (name,) = [p for p in programs if "ehyb_spmv_permuted" in p]
    scopes = {trace_scopes.scope_of(op) for op in programs[name].values()}
    assert {"repro.er.gather", "repro.er.scatter"} <= scopes
    assert "repro.permute" not in scopes


@pytest.mark.parametrize("metric,name", [
    ("build_s", "repro.plan.build"), ("pack_s", "repro.bind.pack")])
def test_program_readers(metric, name, monkeypatch):
    from repro.core import counters

    read = harness.reader(metric)
    rec = {"trace": None, "plan_s": 1.0, "bind_s": 1.0, "ops": 1,
           "iters": 0}
    monkeypatch.setattr(counters, "TIMINGS", {})
    assert read(rec) is None                 # a table without the name
    counters._add(name, 2.5, 2.5, "")
    assert read(rec) == 2.5
    monkeypatch.delattr(counters, "timings")
    assert read(rec) is None                 # a program without the table
