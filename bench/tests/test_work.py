"""The algorithmic work counts and the peaks table."""

import inspect
import json

import numpy as np
import pytest

from bench import work

# A 3×3 matrix with 7 stored entries:  [[4, 1, 0], [1, 4, 1], [0, 1, 4]].
N, NNZ = 3, 7


@pytest.mark.parametrize("k,dtype,nbytes,flops", [
    (1, "float32", 7 * 4 + 3 * 4 + 3 * 4, 14),
    (2, "bfloat16", 7 * 2 + 3 * 2 * 4 + 3 * 2 * 4, 28),
    (8, "float32", 7 * 4 + 3 * 8 * 4 + 3 * 8 * 4, 112),
])
def test_counts_of_a_hand_checked_matrix(k, dtype, nbytes, flops):
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)

    dt = np.dtype(getattr(ml_dtypes, dtype)) if dtype == "bfloat16" else dtype
    assert work.spmv_bytes(N, NNZ, k, dt) == nbytes
    assert work.spmv_flops(NNZ, k) == flops


def test_counts_read_only_the_matrix_sizes():
    """Nothing of a format reaches the counts: only n, nnz, k, dtype."""
    assert list(inspect.signature(work.spmv_bytes).parameters) == [
        "n", "nnz", "k", "dtype"]
    assert list(inspect.signature(work.spmv_flops).parameters) == [
        "nnz", "k"]
    assert list(inspect.signature(work.spmv_min_seconds).parameters) == [
        "n", "nnz", "k", "dtype", "device_kind"]


def test_min_seconds_is_bytes_over_hbm_on_a_v5e():
    n, nnz = 943_296, 74_181_672
    got = work.spmv_min_seconds(n, nnz, 1, "float32", "TPU v5 lite")
    assert got == pytest.approx((nnz * 4 + 2 * n * 4) / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.spmv_min_seconds(3, 7, 1, "float32", "cpu")


def test_every_peak_names_its_source():
    table = json.loads(work.PEAKS.read_text())
    assert table
    for kind, pk in table.items():
        assert pk["source"] and pk["hbm_bytes_per_s"] > 0, kind
        assert pk["bf16_flops_per_s"] > 0, kind


@pytest.mark.parametrize("chips", [1, 4])
def test_roofline_share_counts_the_matrix_once_over_the_cell_s_chips(chips):
    """On several chips each does its share of the matrix's work in the
    busy time of one chip (a mean), so the least time is divided by them."""
    from bench.metrics import device

    n, nnz = 943_296, 74_181_672
    least = work.spmv_min_seconds(n, nnz, 1, "float32", "TPU v5 lite")
    rec = {"trace": {"n_device_ops": 10, "busy_s": 4 * least / chips},
           "ops": 2, "n": n, "nnz": nnz, "k": 1, "dtype": "float32",
           "device_kind": "TPU v5 lite", "chips": chips}
    assert device.spmv_roofline_pct(rec) == pytest.approx(50.0)
