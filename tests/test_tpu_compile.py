"""The main path's Pallas kernels compile for a TPU v5e at HPCG size.

Compiled for a described ``v5e:2x2`` topology (no chip needed): what the
TPU compiler refuses here — block shapes off the (8, 128) tiling, gathers
Mosaic cannot lower, VMEM overruns — fails in this file instead of on the
chip.  Nothing runs, so nothing here is a timing.

Shapes are those of ``symmetrize(poisson3d27(104))`` (HPCG's 104³ local
grid) under ``choose_vec_size``: 552 partitions of 2040 rows, ELL width 27,
at most two (8, 128) tiles per sliced-ELL column.  The ER window is the
benchmark cells' (``natural`` partitions): H = 56 lane-rows; 22 window
columns on HPCG, 63 on the ex56 elasticity matrix's 464 partitions.  A
window at the uint16 cap (H = 512) compiles too, with and without a
leftover for XLA.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.partition import choose_vec_size, lane_geometry

N = 104 ** 3
P, V = choose_vec_size(N)            # (552, 2040)
W = 27                               # 27-point stencil row width
TILES = 2 * W                        # two (8, 128) tiles per column
H = 56                               # ER window lane-rows
WIN = {"hpcg": (P, 22), "ex56": (464, 63)}   # partitions, window columns


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off (a
    described-chip entry cannot be read back without the chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _struct(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _packed_args(one_chip, r):
    _, sb = lane_geometry(V)
    return (_struct(one_chip, (P, V, r), jnp.float32),
            _struct(one_chip, (P, TILES, sb, 128), jnp.float32),
            _struct(one_chip, (P, TILES, sb, 128), jnp.uint16),
            _struct(one_chip, (P, W + 1), jnp.int32),
            _struct(one_chip, (P, W), jnp.int32))


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("k", [1, 8], ids=["spmv", "spmm_k8"])
def test_packed_kernel_compiles_for_v5e(one_chip, k):
    from repro.kernels import ehyb_packed_pallas

    text = _compiled_text(
        lambda *a: ehyb_packed_pallas(*a, interpret=False),
        *_packed_args(one_chip, k))
    assert "tpu_custom_call" in text


def _window_args(one_chip, p, we, r, h=H, tiles=None):
    """x window, tiles and tables of an ER window of h lane-rows: two
    tiles a column unless ``tiles`` is given."""
    _, sb = lane_geometry(V)
    t = 2 * we if tiles is None else tiles
    return (_struct(one_chip, (p, r, h, 128), jnp.float32),
            _struct(one_chip, (p, t, sb, 128), jnp.float32),
            _struct(one_chip, (p, t, sb, 128), jnp.uint16),
            _struct(one_chip, (p, we + 1), jnp.int32),
            _struct(one_chip, (p, we), jnp.int32))


@pytest.mark.parametrize("cell", ["hpcg", "ex56"])
@pytest.mark.parametrize("k", [1, 8], ids=["spmv", "spmm_k8"])
def test_er_window_kernel_compiles_for_v5e(one_chip, cell, k):
    """The ER window's call (x block of H lane-rows) next to the
    own-slice call, at the cells' geometry."""
    from repro.kernels import ehyb_packed_pallas

    p, we = WIN[cell]
    _, sb = lane_geometry(V)
    own = (_struct(one_chip, (p, V, k), jnp.float32),
           _struct(one_chip, (p, TILES, sb, 128), jnp.float32),
           _struct(one_chip, (p, TILES, sb, 128), jnp.uint16),
           _struct(one_chip, (p, W + 1), jnp.int32),
           _struct(one_chip, (p, W), jnp.int32))
    text = _compiled_text(
        lambda own, win: ehyb_packed_pallas(*own, er_window=win,
                                            interpret=False),
        own, _window_args(one_chip, p, we, k))
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("k", [1, 16], ids=["spmv", "spmm_k16"])
def test_tall_er_window_compiles_for_v5e(one_chip, k):
    """A window at the uint16 cap (512 lane-rows) over few tiles: the call
    sizes its grid step and rhs chunk by the x and output blocks too, so
    the step fits VMEM."""
    from repro.kernels import ehyb_packed_pallas

    x_win, *tables = _window_args(one_chip, P, 1, k, h=512, tiles=2)
    text = _compiled_text(
        lambda own, win: ehyb_packed_pallas(*own, er_window=win,
                                            interpret=False),
        _packed_args(one_chip, k), (x_win, *tables))
    assert text.count("tpu_custom_call") >= 2


def _device(one_chip, k, h, leftover):
    from repro.core.spmv import EHYBPackedDevice

    _, vals, cols, starts, rows = _packed_args(one_chip, k)
    _, *win = _window_args(one_chip, P, WIN["hpcg"][1], k, h=h)
    n_pad = P * V
    i32 = jnp.int32
    e, we = 1024, 19                 # leftover rows a partition, width
    left = ((_struct(one_chip, (P, e, we), jnp.float32),
             _struct(one_chip, (P, e, we), i32),
             _struct(one_chip, (P, e), i32)) if leftover else (None,) * 3)
    return EHYBPackedDevice(
        n=N, n_pad=n_pad, n_parts=P, vec_size=V, has_er=True,
        packed_vals=vals, packed_cols=cols, col_starts=starts, col_rows=rows,
        er_vals=_struct(one_chip, (8, we), jnp.float32),
        er_cols=_struct(one_chip, (8, we), i32),
        er_row_idx=_struct(one_chip, (8,), i32),
        er_p_vals=left[0], er_p_cols=left[1], er_p_rows=left[2],
        win_rows=_struct(one_chip, (P, h), i32),
        win_vals=win[0], win_cols=win[1], win_starts=win[2],
        win_col_rows=win[3],
        perm=_struct(one_chip, (n_pad,), i32),
        inv_perm=_struct(one_chip, (n_pad,), i32))


@pytest.mark.parametrize("k", [1, 8], ids=["spmv", "spmm_k8"])
def test_permuted_apply_compiles_for_v5e(one_chip, k):
    """The format's whole permuted apply: the kernel over the own slices
    and over the ER windows, with the window gather in XLA."""
    from repro.kernels import ehyb_spmv_packed_pallas_permuted

    shape = (P * V,) if k == 1 else (P * V, k)
    text = _compiled_text(
        lambda d, v: ehyb_spmv_packed_pallas_permuted(d, v, interpret=False),
        _device(one_chip, k, H, leftover=False),
        _struct(one_chip, shape, jnp.float32))
    assert text.count("tpu_custom_call") >= 2


def test_permuted_apply_with_leftover_compiles_for_v5e(one_chip):
    """A 512-lane-row window with a leftover: the XLA ER stage runs beside
    both kernel calls."""
    from repro.kernels import ehyb_spmv_packed_pallas_permuted

    text = _compiled_text(
        lambda d, v: ehyb_spmv_packed_pallas_permuted(d, v, interpret=False),
        _device(one_chip, 1, 512, leftover=True),
        _struct(one_chip, (P * V,), jnp.float32))
    assert text.count("tpu_custom_call") >= 2


def test_fused_cg_update_compiles_for_v5e(one_chip):
    from repro.kernels.solver_step import _fused_cg_update

    v = _struct(one_chip, (P * V,), jnp.float32)
    text = _compiled_text(
        lambda *a: _fused_cg_update(*a, interpret=False),
        v, v, v, v, v, _struct(one_chip, (), jnp.float32))
    assert "tpu_custom_call" in text
