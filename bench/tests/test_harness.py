"""CPU rehearsal of the harness: everything loads by name, the generators
build the program's own matrices, a small run is correct, and no result is
printed off a TPU."""

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.tests.cells import CELLS, run_small

ROOT = harness.ROOT
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"] and BM["command"][1] == "bench/run.py"
    names = ([c["name"] for c in BM["configs"]]
             + [w["name"] for w in BM["workloads"]]
             + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    assert "setup_s" in {m["name"] for m in BM["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_loads_by_name(cell):
    spec = harness.load_cell(cell, BM)
    cfg = spec["config"]
    assert cfg["name"] == next(w["config"] for w in BM["workloads"]
                               if w["name"] == cell)
    assert spec["cell"]["name"] == cell and spec["cell"]["limits"]
    assert hasattr(harness.generator(cfg), "generate")
    drv = harness.driver(spec["traffic"])
    for fn in ("call", "tally", "checks", "end_to_end"):
        assert callable(getattr(drv, fn))
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e, (cell, m["name"])


@pytest.mark.parametrize("cfg", BM["configs"], ids=lambda c: c["name"])
def test_every_configuration_states_its_size(cfg):
    """The file's ``n`` and ``nnz`` are those its generator builds (counted
    from the grid, not generated at full size)."""
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"]
    assert set(cfg["reduced"]) <= set(data)
    grid = data.get("grid") or data["node_grid"]
    dofs = data.get("dofs_per_node", 1)
    assert data["n"] == int(np.prod(grid)) * dofs
    assert data["nnz"] == int(np.prod([3 * g - 2 for g in grid])) * dofs ** 2


@pytest.mark.parametrize("metric", BM["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_its_reader(metric):
    read = harness.reader(metric["name"])
    rec = {"trace": None, "plan_s": 1.5, "bind_s": 2.5, "ops": 4,
           "iters": 0, "n": 8, "nnz": 20, "k": 1, "dtype": "float32",
           "device_kind": "TPU v5 lite"}
    got = read(rec)
    if metric["source"] == "device_trace":
        assert got is None            # nothing to read, never a 0
    for c in metric["workloads"]:
        assert c in {w["name"] for w in BM["workloads"]}


def _small_cfg(name, **grid):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    cfg.update(grid)
    return cfg


@pytest.mark.parametrize("g", [3, 5])
def test_hpcg_generator_matches_the_program_pattern(g):
    from repro.core.matrices import poisson3d27, symmetrize

    cfg = _small_cfg("hpcg-104", grid=[g, g, g])
    a = harness.generator(cfg).generate(cfg)
    m = symmetrize(poisson3d27(g))
    np.testing.assert_array_equal(a["indptr"], m.indptr)
    np.testing.assert_array_equal(a["indices"], m.indices)
    _assert_symmetric_spd(a)
    s = _csr(a)
    np.testing.assert_array_equal(s.diagonal(), 26.0)
    assert set(np.unique(s.data)) == {-1.0, 26.0}


@pytest.mark.parametrize("g", [3, 4])
def test_elasticity_generator_matches_the_program_pattern(g):
    from repro.core.matrices import elasticity3d

    cfg = _small_cfg("petsc-ex56-ne67", node_grid=[g, g, g])
    a = harness.generator(cfg).generate(cfg)
    m = elasticity3d(g)
    np.testing.assert_array_equal(a["indptr"], m.indptr)
    np.testing.assert_array_equal(a["indices"], m.indices)
    _assert_symmetric_spd(a)


def _csr(a):
    import scipy.sparse as sp

    return sp.csr_matrix((a["data"], a["indices"], a["indptr"]))


def _assert_symmetric_spd(a):
    s = _csr(a)
    assert abs(s - s.T).max() == 0.0
    assert np.linalg.eigvalsh(s.toarray()).min() > 0


def test_q1_element_has_the_six_rigid_body_modes():
    from bench.gen import elasticity_q1 as q1

    k = q1.element_stiffness(0.5, 1.0, 0.25).reshape(24, 24)
    assert abs(k - k.T).max() < 1e-15
    eig = np.linalg.eigvalsh(k)
    assert np.all(np.abs(eig[:6]) < 1e-12) and eig[6] > 1e-3
    x, y, _ = q1._CORNERS.T.astype(float)
    rotation = np.stack([-y, x, 0 * x], axis=1).ravel()
    assert abs(k @ rotation).max() < 1e-12


@pytest.mark.parametrize("g", [3, 4])
def test_elasticity_values_are_the_assembled_q1_stiffness(g):
    """The table fill equals element-by-element assembly, with the nodes of
    y = 0 fixed (diagonal kept, the rest of row and column 0)."""
    from bench.gen import elasticity_q1 as q1

    cfg = _small_cfg("petsc-ex56-ne67", node_grid=[g, g, g])
    got = _csr(harness.generator(cfg).generate(cfg)).toarray()
    ke = q1.element_stiffness(1.0 / (g - 1), cfg["youngs_modulus"],
                              cfg["poisson_ratio"]).reshape(24, 24)
    want = np.zeros_like(got)
    for e in np.ndindex(g - 1, g - 1, g - 1):
        nodes = [np.ravel_multi_index(tuple(np.add(e, c)), (g, g, g))
                 for c in q1._CORNERS]
        dof = (3 * np.array(nodes)[:, None] + np.arange(3)).ravel()
        want[np.ix_(dof, dof)] += ke
    fixed = np.repeat(np.unravel_index(np.arange(g ** 3), (g, g, g))[1] == 0,
                      3)
    diag = want.diagonal().copy()
    want[fixed, :] = 0.0
    want[:, fixed] = 0.0
    want[np.diag_indices_from(want)] = diag
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-15)


def test_generator_depends_on_the_configuration_only():
    cfg = _small_cfg("hpcg-104", grid=[4, 4, 4])
    gen = harness.generator(cfg)
    a, b = gen.generate(cfg), gen.generate(dict(cfg))
    np.testing.assert_array_equal(a["data"], b["data"])
    c = gen.generate({**cfg, "diagonal": cfg["diagonal"] + 1})
    assert not np.array_equal(a["data"], c["data"])


def test_reference_cg_converges_to_the_solution():
    """Run long enough, the reference's CG solves the system; the float64
    loop is the yardstick the fixed-iteration solves are held to."""
    from bench import reference

    cfg = _small_cfg("hpcg-104", grid=[5, 5, 5])
    a = reference.csr64(harness.generator(cfg).generate(cfg))
    b = np.random.default_rng(0).standard_normal(a.shape[0])
    x = reference.cg(a, b, 60)
    want = np.linalg.solve(a.toarray(), b)
    assert reference.relative_error(x, want) < 1e-12


def test_pool_is_drawn_from_every_bit_of_the_seed():
    p1 = harness.make_pool(16, 1, 5, 2)
    p2 = harness.make_pool(16, 1, 5 + (1 << 33), 2)
    p3 = harness.make_pool(16, 1, 5, 2)
    assert not np.array_equal(p1[0], p2[0])
    np.testing.assert_array_equal(p1[1], p3[1])
    assert harness.make_pool(16, 4, 5, 1)[0].shape == (16, 4)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_small_run_is_correct(cell, trace, tmp_path):
    (r,) = run_small(cell, tmp_path, trace=trace)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    spec = harness.load_cell(cell, BM)
    want = spec["per_layer"] if trace else spec["end_to_end"]
    host = {m["name"] for m in want if m["source"] != "device_trace"}
    assert host <= set(r["metrics"]) <= {m["name"] for m in want}
    for m in want:
        if m["name"] in r["metrics"]:
            assert r["metrics"][m["name"]]["unit"] == m["unit"]
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(r["device"])
    if trace:
        assert {"busy_s", "window_s"} <= set(r["device"])
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_states_its_cut(cell):
    """The rehearsal's cut is the cell's own data: keys of its
    configuration, in ``bench/workloads/<cell>.json``, not in the code."""
    spec = harness.load_cell(cell, BM)
    small = spec["cell"]["small"]
    assert small and set(small) <= set(spec["config"])
    assert cell not in (ROOT / "bench" / "tests" / "cells.py").read_text()


@pytest.mark.parametrize("trace", [False, True])
def test_hpcg_spec_on_four_devices_plans_over_the_mesh(trace, tmp_path):
    """``hpcg.cg``'s spec with ``chips`` 4, on 4 virtual CPU devices at an
    8 x 8 x 4 grid (n = 256: eight partitions, 64 rows a device, a halo
    exchanged every apply): planned over the mesh in the ``"dist"``
    context, and its solves correct.  A CPU trace holds no TPU plane, so
    ``busy_s_by_chip`` is empty there (4 entries on four TPUs)."""
    spec = harness.load_cell("hpcg.cg", BM)
    spec["chips"] = 4
    spec["config"]["grid"] = [8, 8, 4]
    log = []
    (r,) = run_small("hpcg.cg", tmp_path, spec=spec, trace=trace,
                     log=log.append)
    assert any("chips=4 sharded=True" in m for m in log), log
    assert r["correct"], r["checks"]
    assert r["checks"]["x_err"]["value"] < r["checks"]["x_err"]["limit"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert r["device"]["count"] == 4
    if trace:
        assert r["device"]["busy_s_by_chip"] == []


def _run_py(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu"}, **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpcg.cg", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_result_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  ".tune_store"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "cannot load" in p.stderr
