"""Paper §3.4 claim: the compact (uint16) column index + explicit caching cut
SpMV HBM traffic ~25 % (fp32) / ~13.3 % (fp64) vs 32-bit-index formats.

Bytes are modeled per format (hardware-independent) and converted to a
TPU-v5e roofline time (819 GB/s HBM) — SpMV is memory-bound so
bytes ≈ runtime.  CSR x-traffic is bracketed between the two classical
bounds: perfect cache (each x value read once) and no cache (one read per
nnz); EHYB's cached reads are *exact* (one VMEM fill per partition), which is
the paper's point.
"""

from __future__ import annotations

from repro.core import SUITE, build_buckets

from .common import emit, get_ehyb, get_matrix

HBM = 819e9


def csr_bytes(m, val_bytes, perfect_cache):
    idx = 4 * m.nnz + 4 * (m.n + 1)
    vals = val_bytes * m.nnz
    x = val_bytes * (m.n if perfect_cache else m.nnz)
    y = val_bytes * m.n
    return idx + vals + x + y


def main():
    out = {}
    for name in SUITE:
        m = get_matrix(name)
        e = get_ehyb(name)
        b = build_buckets(e)
        for vb, prec in ((4, "f32"), (8, "f64")):
            ehyb = e.bytes_moved(vb)["total"]            # paper's sliced-ELL
            ehyb32 = e.bytes_moved(vb, col_bytes=4)["total"]  # int32 ablation
            etile = e.bytes_moved(vb, layout="tile")["total"]  # XLA tiles
            epack = e.bytes_moved(vb, layout="packed")["total"]  # Pallas
            ebuck = b.bytes_moved(vb)["total"]
            lo = csr_bytes(m, vb, True)
            hi = csr_bytes(m, vb, False)
            rec = {"ehyb_sliced": ehyb, "ehyb_int32": ehyb32,
                   "ehyb_tile": etile, "ehyb_packed": epack,
                   "ehyb_bucketed": ebuck, "csr_best": lo, "csr_worst": hi,
                   "saving_vs_csr_best": 1 - ehyb / lo,
                   "saving_vs_csr_worst": 1 - ehyb / hi,
                   "int16_saving": 1 - ehyb / ehyb32}
            out[(name, prec)] = rec
            emit(f"bytes_{prec}/{name}", ehyb / HBM * 1e6,
                 f"sliced={ehyb};tile={etile};packed={epack};"
                 f"bucketed={ebuck};csr_best={lo};csr_worst={hi};"
                 f"int16_saving={rec['int16_saving']:.3f};"
                 f"vs_csr_best={rec['saving_vs_csr_best']:.3f};"
                 f"vs_csr_worst={rec['saving_vs_csr_worst']:.3f}")
    return out


if __name__ == "__main__":
    main()
