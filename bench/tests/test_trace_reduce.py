"""The reduction from trace events to device seconds."""

import json
import pathlib

import pytest

from bench import trace_reduce, trace_scopes

DATA = pathlib.Path(__file__).resolve().parent / "data"
KERNELS = ("ehyb_packed_spmv", "fused_cg_update")

# Nanoseconds.  Window [100, 200).  Device ops: an XLA fusion, the SpMV
# kernel, a loop op with the CG-update kernel nested in it, an op that
# overlaps the window's start and one that lies wholly after it.
SYNTHETIC = {
    "device": [
        ["fusion.1", 90.0, 20.0],                       # clipped to [100, 110)
        ["ehyb_packed_spmv.3", 110.0, 5.0],
        ["while.2", 120.0, 20.0],
        ["fused_cg_update", 125.0, 5.0],                 # nested in while.2
        ["fusion.1", 150.0, 10.0],
        ["copy.9", 250.0, 10.0],                         # after the window
    ],
    "host": [
        ["bench.window", 100.0, 100.0],
        ["bench.solve", 100.0, 70.0],
        ["bench.check", 180.0, 30.0],
    ],
}


def test_busy_is_the_union_of_device_intervals():
    s = trace_reduce.reduce(SYNTHETIC, KERNELS)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx((10 + 5 + 20 + 10) * 1e-9)
    assert s["n_device_ops"] == 5


def test_kernels_by_name_and_the_rest_by_self_time():
    s = trace_reduce.reduce(SYNTHETIC, KERNELS)
    assert s["kernel_s"]["ehyb_packed_spmv"] == pytest.approx(5e-9)
    assert s["kernel_s"]["fused_cg_update"] == pytest.approx(5e-9)
    # fusion.1: 10 + 10; while.2: 20 less the 5 of its nested kernel
    assert s["other_s"] == pytest.approx(35e-9)
    total = sum(s["kernel_s"].values()) + s["other_s"]
    assert total == pytest.approx(s["busy_s"])
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(20e-9)]


def test_idle_gaps_go_to_the_covering_host_span():
    s = trace_reduce.reduce(SYNTHETIC, KERNELS)
    gaps = dict(s["idle_gaps"])
    # [115, 120) and [140, 150) lie in bench.solve; [160, 200): its middle,
    # 180, lies in bench.check
    assert gaps["bench.solve"] == pytest.approx(15e-9)
    assert gaps["bench.check"] == pytest.approx(40e-9)
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_uncovered_gaps_are_the_host_loop():
    rec = {"device": [["fusion", 0.0, 10.0], ["fusion", 30.0, 10.0]],
           "host": [["bench.window", 0.0, 40.0]]}
    s = trace_reduce.reduce(rec, KERNELS)
    assert dict(s["idle_gaps"]) == {"host.loop": pytest.approx(20e-9)}


def test_no_device_events_reads_zero_busy():
    s = trace_reduce.reduce({"device": [], "host": []}, KERNELS)
    assert s["busy_s"] == 0 and s["n_device_ops"] == 0


@pytest.mark.parametrize("text,label", [
    ("%fusion.10 = f32[24773760]{0:T(1024)S(1)} fusion(f32[1126080] "
     "%copy-done.1), kind=kCustom, calls=%fused_computation.clone",
     "fusion.10"),
    ("%custom-call.2 = f32[552,2040]{1,0} custom-call(...), "
     "custom_call_target=\"tpu_custom_call\", op_name=\"ehyb_packed_spmv\"",
     "ehyb_packed_spmv"),
    ("%ehyb_packed_spmv.9 = f32[552,16,16,128]{3,2,1,0} custom-call(...), "
     "custom_call_target=\"tpu_custom_call\"", "ehyb_packed_spmv"),
    # consumers of the kernel's output are not the kernel
    ("%reshape.177 = f32[1126080]{0} reshape(f32[552,2040]{1,0} "
     "%ehyb_packed_spmv.9)", "reshape.177"),
    ("%add_fusion.4 = f32[1126080]{0} fusion(f32[1126080]{0} %reshape.177, "
     "f32[552,2040]{1,0} %ehyb_packed_spmv.9), kind=kLoop, "
     "calls=%fused_add.4", "add_fusion.4"),
    ("copy.3", "copy.3"),
])
def test_op_label(text, label):
    assert trace_reduce.op_label(text, KERNELS) == label


def _recorded():
    """0.9 s of a traced ``hpcg.cg`` window on a TPU v5 lite: the end of
    one solve, the host's work between two solves, and the start of the
    next, times rebased to 0."""
    import gzip

    with gzip.open(DATA / "hpcg_cg_trace.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_trace_splits_busy_time_exactly():
    s = trace_reduce.reduce(_recorded(), KERNELS)
    assert s["window_s"] == pytest.approx(0.90002924)
    assert s["busy_s"] == pytest.approx(0.449087275)
    # the CG loop op spans the solve: its nested ops are not counted twice
    total = sum(s["kernel_s"].values()) + s["other_s"]
    assert total == pytest.approx(s["busy_s"])
    assert s["kernel_s"]["ehyb_packed_spmv"] == pytest.approx(0.002595632)
    assert s["kernel_s"]["fused_cg_update"] == pytest.approx(1.9653e-05)
    assert s["device_ops"][0] == ["fusion.10", pytest.approx(0.397328896)]


def test_recorded_trace_idle_lies_in_the_solve_calls():
    s = trace_reduce.reduce(_recorded(), KERNELS)
    assert dict(s["idle_gaps"]) == {
        "bench.solve": pytest.approx(s["window_s"] - s["busy_s"])}


# The parent commit's reduction of the recorded TPU v5 lite trace
# ``hpcg16.xplane.pb.gz``, frozen: ``trace_reduce`` (harness spans only),
# and where ``trace_scopes`` (program spans too) differs or adds.
ONE_CHIP = {
    "window_s": 0.049565318000000004,
    "busy_s": 0.01133257,
    "n_device_ops": 1299,
    "kernel_s": {"ehyb_packed_spmv": 0.0005696340000000001,
                 "fused_cg_update": 4.77e-07},
    "other_s": 0.010762459,
    "device_ops": [["fusion.9", 0.007891963],
                   ["fusion.10", 0.001081682],
                   ["fusion.2", 0.000897856],
                   ["ehyb_packed_spmv", 0.0005696340000000001],
                   ["fusion", 0.000299806],
                   ["while.1", 0.000146025],
                   ["fusion.3", 0.000108134],
                   ["fusion.1", 0.00010654700000000001],
                   ["reshape.129", 8.8953e-05],
                   ["reduce.21", 3.1744e-05]],
    "idle_gaps": [["bench.solve", 0.035635179],
                  ["bench.apply", 0.002597569]],
}
ONE_CHIP_SCOPES = {
    **ONE_CHIP,
    "idle_gaps": [["repro.solve.to_space", 0.018929671000000002],
                  ["repro.solve.finalize", 0.008008118],
                  ["repro.solve.from_space", 0.005476702],
                  ["bench.apply", 0.002597569],
                  ["repro.solve.key", 0.001991805],
                  ["repro.solve.precond", 0.0012266760000000001],
                  ["repro.solve", 2.2070000000000002e-06]],
    "scope_s": {"repro.er": 0.008735409000000001,
                "repro.er.gather": 0.008734104000000001,
                "repro.er.scatter": 1.305e-06,
                "repro.permute": 0.000195326},
    "span_idle_s": {"repro.solve.to_space": 0.01894266,
                    "repro.solve.from_space": 0.007576249,
                    "repro.solve.finalize": 0.004778699,
                    "repro.solve.key": 0.002865788,
                    "bench.apply": 0.002589749,
                    "repro.solve": 0.0011068430000000001,
                    "bench.solve": 0.00019490000000000002,
                    "repro.solve.precond": 0.00016082000000000002,
                    "host.loop": 1.704e-05},
}


@pytest.mark.parametrize("module,want", [("trace_reduce", ONE_CHIP),
                                         ("trace_scopes", ONE_CHIP_SCOPES)])
def test_one_chip_reduction_of_the_recorded_chip_trace_is_unchanged(
        module, want, tmp_path):
    """Reading one chip, every key the reducers gave before they read
    several equals what they gave then, to the last bit."""
    import gzip
    import importlib
    import shutil

    xspace = tmp_path / "hpcg16.xplane.pb"
    with gzip.open(DATA / "hpcg16.xplane.pb.gz", "rb") as f, \
            open(xspace, "wb") as out:
        shutil.copyfileobj(f, out)
    mod = importlib.import_module(f"bench.{module}")
    got = mod.reduce(mod.extract(xspace, KERNELS, chips=1), KERNELS)
    assert {k: got[k] for k in want} == want
    assert got["busy_s_by_chip"] == [got["busy_s"]]
    assert got["collective_s"] == 0.0


# Nanoseconds.  Two chips, window [0, 100).  Chip 0: an XLA fusion, the
# SpMV kernel and an all-to-all; chip 1: the fusion later, and the two
# halves of an all-reduce.  Busy: chip 0 40, chip 1 30; the union of both
# is [10, 50) and [60, 80).
TWO_CHIPS = {
    "chips": 2,
    "device": [
        ["fusion.1", 10.0, 20.0, 0],
        ["ehyb_packed_spmv.3", 40.0, 10.0, 0],
        ["all-to-all.3", 60.0, 10.0, 0],
        ["fusion.1", 20.0, 20.0, 1],
        ["all-reduce-start.2", 70.0, 5.0, 1],
        ["all-reduce-done.2", 75.0, 5.0, 1],
    ],
    "host": [
        ["bench.window", 0.0, 100.0],
        ["bench.solve", 0.0, 85.0],
    ],
}


def test_two_chips_reduce_to_means_and_idle_against_the_union():
    s = trace_reduce.reduce(TWO_CHIPS, KERNELS)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s_by_chip"] == [pytest.approx(40e-9),
                                   pytest.approx(30e-9)]
    assert s["busy_s"] == pytest.approx(35e-9)
    assert s["n_device_ops"] == 6
    assert s["kernel_s"]["ehyb_packed_spmv"] == pytest.approx(5e-9)
    assert s["other_s"] == pytest.approx(30e-9)
    assert s["collective_s"] == pytest.approx(10e-9)
    assert dict(s["device_ops"]) == {
        "fusion.1": pytest.approx(20e-9), "all-to-all.3": pytest.approx(5e-9),
        "ehyb_packed_spmv.3": pytest.approx(5e-9),
        "all-reduce-start.2": pytest.approx(2.5e-9),
        "all-reduce-done.2": pytest.approx(2.5e-9)}
    # idle only where neither chip runs: [0, 10) and [50, 60) in
    # bench.solve, [80, 100) (middle 90) after it
    assert dict(s["idle_gaps"]) == {"bench.solve": pytest.approx(20e-9),
                                    "host.loop": pytest.approx(20e-9)}
    scoped = {**TWO_CHIPS, "device": [
        [*e[:3], "repro.er" if e[0] == "fusion.1" else "", e[3]]
        for e in TWO_CHIPS["device"]]}
    t = trace_scopes.reduce(scoped, KERNELS)
    assert {k: t[k] for k in s} == s
    assert t["scope_s"] == {"repro.er": pytest.approx(20e-9)}
    # split at the span's end, 85
    assert t["span_idle_s"] == {"bench.solve": pytest.approx(25e-9),
                                "host.loop": pytest.approx(15e-9)}


def test_a_chip_without_ops_counts_in_the_means():
    rec = {"chips": 2, "device": [e for e in TWO_CHIPS["device"]
                                  if e[3] == 0],
           "host": TWO_CHIPS["host"]}
    s = trace_reduce.reduce(rec, KERNELS)
    assert s["busy_s_by_chip"] == [pytest.approx(40e-9), 0.0]
    assert s["busy_s"] == pytest.approx(20e-9)
    assert s["collective_s"] == pytest.approx(5e-9)


@pytest.mark.parametrize("name,collective", [
    ("all-to-all.3", True), ("all-reduce", True), ("all-reduce-start.2", True),
    ("all-gather-done.1", True), ("reduce-scatter.4", True),
    ("collective-permute-start", True), ("fusion.1", False),
    ("reduce.21", False), ("all-reduce-fusion.2", False)])
def test_collectives_by_the_op_s_own_name(name, collective):
    assert trace_reduce.is_collective(name) is collective


def test_tpu_planes_in_the_order_of_their_device_id():
    class Plane:
        def __init__(self, name):
            self.name = name

    class Profile:
        planes = [Plane(n) for n in ("/device:TPU:10", "/host:CPU",
                                     "/device:TPU:2", "#Chip0 Misc",
                                     "/device:TPU:0", "/device:TPU:1")]

    names = [p.name for p in trace_reduce.tpu_planes(Profile, 3)]
    assert names == ["/device:TPU:0", "/device:TPU:1", "/device:TPU:2"]
    assert [p.name for p in trace_reduce.tpu_planes(Profile, 8)][-1] == (
        "/device:TPU:10")
