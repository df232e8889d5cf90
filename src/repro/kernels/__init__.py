"""Pallas TPU kernels for the paper's compute hot-spot: EHYB SpMV/SpMM.

ehyb_spmv.py   — the packed-staircase kernel: lane-dense x-slice cached in
                 VMEM per partition, uint16 local columns gathered inside
                 the vreg; one kernel for one rhs and for a (n_pad, K) batch.
ops.py         — jit'd public wrappers (interpret=True on CPU); the
                 ``*_permuted`` variant is the solver hot-loop entry point.
solver_step.py — fused CG vector-update kernel (axpy + preconditioner apply
                 + both dot reductions in one HBM pass).
ref.py         — pure-jnp oracles used by the allclose test sweeps.
"""

from .ehyb_spmv import ehyb_packed_pallas
from .ops import (backend_supports_pallas, ehyb_spmv_packed_pallas,
                  ehyb_spmv_packed_pallas_permuted)
from .solver_step import fused_cg_update
from . import ref

__all__ = ["ehyb_packed_pallas", "backend_supports_pallas",
           "ehyb_spmv_packed_pallas", "ehyb_spmv_packed_pallas_permuted",
           "fused_cg_update", "ref"]
