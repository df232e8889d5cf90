"""The main path's Pallas kernels compile for a TPU v5e at HPCG size.

Compiled for a described ``v5e:2x2`` topology (no chip needed): what the
TPU compiler refuses here — block shapes off the (8, 128) tiling, gathers
Mosaic cannot lower, VMEM overruns — fails in this file instead of on the
chip.  Nothing runs, so nothing here is a timing.

Shapes are those of ``symmetrize(poisson3d27(104))`` (HPCG's 104³ local
grid) under ``choose_vec_size``: 552 partitions of 2040 rows, ELL width 27,
at most two (8, 128) tiles per sliced-ELL column.
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
E, WE = 1024, 19                     # ER rows per partition, ER width


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


@pytest.mark.parametrize("k", [1, 8], ids=["spmv", "spmm_k8"])
def test_permuted_apply_compiles_for_v5e(one_chip, k):
    """The format's whole permuted apply: the kernel plus the XLA ER stage."""
    from repro.core.spmv import EHYBPackedDevice
    from repro.kernels import ehyb_spmv_packed_pallas_permuted

    x, vals, cols, starts, rows = _packed_args(one_chip, k)
    n_pad = P * V
    i32 = jnp.int32
    dev = EHYBPackedDevice(
        n=N, n_pad=n_pad, n_parts=P, vec_size=V, has_er=True,
        packed_vals=vals, packed_cols=cols, col_starts=starts, col_rows=rows,
        er_vals=_struct(one_chip, (8, WE), jnp.float32),
        er_cols=_struct(one_chip, (8, WE), i32),
        er_row_idx=_struct(one_chip, (8,), i32),
        er_p_vals=_struct(one_chip, (P, E, WE), jnp.float32),
        er_p_cols=_struct(one_chip, (P, E, WE), i32),
        er_p_rows=_struct(one_chip, (P, E), i32),
        perm=_struct(one_chip, (n_pad,), i32),
        inv_perm=_struct(one_chip, (n_pad,), i32))
    shape = (n_pad,) if k == 1 else (n_pad, k)
    text = _compiled_text(
        lambda d, v: ehyb_spmv_packed_pallas_permuted(d, v, interpret=False),
        dev, _struct(one_chip, shape, jnp.float32))
    assert "tpu_custom_call" in text


def test_fused_cg_update_compiles_for_v5e(one_chip):
    from repro.kernels.solver_step import _fused_cg_update

    v = _struct(one_chip, (P * V,), jnp.float32)
    text = _compiled_text(
        lambda *a: _fused_cg_update(*a, interpret=False),
        v, v, v, v, v, _struct(one_chip, (), jnp.float32))
    assert "tpu_custom_call" in text
