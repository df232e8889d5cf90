"""The reduction from trace events to device seconds."""

import json
import pathlib

import pytest

from bench import trace_reduce

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
