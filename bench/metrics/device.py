"""Shared arithmetic of the device-trace readers."""

from __future__ import annotations

from bench import work


def _trace(rec):
    t = rec.get("trace")
    return t if t and t["n_device_ops"] and t["busy_s"] > 0 else None


def per(rec, seconds, count_key):
    """``seconds`` in milliseconds per call (``ops``) or per solver
    iteration (``iters``); None when there is nothing to divide."""
    count = rec.get(count_key) or 0
    if seconds is None or seconds <= 0 or count <= 0:
        return None
    return seconds * 1e3 / count


def kernel_ms(rec, kernel, count_key):
    t = _trace(rec)
    return None if t is None else per(rec, t["kernel_s"].get(kernel),
                                      count_key)


def other_ms(rec, count_key):
    """Device ms of every op that is neither Pallas kernel."""
    t = _trace(rec)
    return None if t is None else per(rec, t["other_s"], count_key)


def idle_pct(rec):
    """100 · (1 − busy ÷ traced window)."""
    t = _trace(rec)
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def spmv_roofline_pct(rec):
    """100 · (least time of one apply on the cell's chips ÷ device busy
    time per apply, a chip's mean), the work being the matrix's
    (``bench.work``), shared evenly by the chips."""
    t = _trace(rec)
    if t is None or not rec.get("ops"):
        return None
    least = work.spmv_min_seconds(rec["n"], rec["nnz"], rec["k"],
                                  rec["dtype"], rec["device_kind"])
    return 100.0 * least / rec.get("chips", 1) / (t["busy_s"] / rec["ops"])
