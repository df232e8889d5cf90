"""Distributed halo exchange vs the all-gather baseline (kind:"dist").

For every suite matrix × mesh size this module records, per SpMV iteration:

* ``halo_words``        — the sharded operator's scheduled exchange payload
                          (the compact halo: Σ over device pairs of
                          min(unique fetched columns, unique pushed rows));
* ``allgather_words``   — the words the replaced ``dist_spmv``
                          implementation moved (full x all-gather + full
                          psum-scatter, ``2·n_dev·n_pad``);
* ``coll_bytes_*``      — both implementations compiled on the mesh and
                          measured with the roofline HLO cost parser
                          (these include the ``all_to_all``'s padding and
                          self-segment, so the halo side is an upper bound
                          on physical interconnect bytes);
* distributed vs local ``solve()`` wall time and residuals (the
  correctness contract: same tolerance, same trajectory).

Everything runs in this process on ``jax.devices()``: one process per
chip, so the device is never held by a parent while a child needs it.  On
the CPU the devices are virtual, from the caller's
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (as the CI
``multi-device`` job sets it); mesh sizes larger than the device count are
skipped.  ``main()`` returns the records that ``benchmarks/run.py`` commits
to ``BENCH_spmv.json``.
"""

from __future__ import annotations

DEFAULT_MATRICES = ("poisson3d_16", "poisson3d_24", "poisson27_12",
                    "elasticity_8", "unstruct_4k", "powerlaw_4k",
                    "powerlaw_8k")
QUICK_MATRICES = ("poisson3d_16", "powerlaw_4k")
DEFAULT_NDEV = (4, 8)
QUICK_NDEV = (4,)


def _measure(matrices, n_devs, max_iters: int) -> list:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.core import SUITE
    from repro.dist import build_allgather_spmv
    from repro.roofline.hlo_cost import analyze_hlo

    ehyb = api.ExecutionConfig(format="ehyb")
    records = []
    for name in matrices:
        m = SUITE[name]()
        rng = np.random.default_rng(0)
        b = jnp.asarray(rng.standard_normal(m.n), dtype=jnp.float32)
        op = api.plan(m, execution=ehyb).bind(m)
        sol = api.plan(m, execution=api.ExecutionConfig(
            format="ehyb", workload="solver")).bind(m)
        r_loc = sol.solve(b, precond="jacobi", max_iters=max_iters)
        jax.block_until_ready(r_loc.x)          # warm the compile cache
        t0 = time.perf_counter()
        r_loc = sol.solve(b, precond="jacobi", max_iters=max_iters)
        jax.block_until_ready(r_loc.x)
        t_loc = time.perf_counter() - t0
        for n_dev in n_devs:
            mesh_shape = (n_dev,)
            from repro.compat import make_mesh

            mesh = make_mesh(mesh_shape, ("data",))
            sop = api.plan(m, mesh=mesh, execution=ehyb).bind(m)
            plan = sop.halo_plan
            xp = sop.to_permuted(b)
            halo_hlo = (jax.jit(sop.matvec_permuted).lower(xp).compile()
                        .as_text())
            coll_halo = int(analyze_hlo(halo_hlo)["coll_bytes"])
            if op.obj.n_parts % n_dev == 0:
                # the baseline has no partition padding; on a non-divisible
                # combination only the halo path runs (record nulls rather
                # than aborting the whole sweep)
                legacy = build_allgather_spmv(op.obj, mesh, "data",
                                              space="permuted")
                xl = xp[: op.obj.n_pad]
                leg_hlo = jax.jit(legacy).lower(xl).compile().as_text()
                coll_leg = int(analyze_hlo(leg_hlo)["coll_bytes"])
            else:
                coll_leg = None
            # distributed solve: compile, then time one solve
            r_dist = sop.solve(b, precond="jacobi", max_iters=max_iters)
            jax.block_until_ready(r_dist.x)
            t0 = time.perf_counter()
            r_dist = sop.solve(b, precond="jacobi", max_iters=max_iters)
            jax.block_until_ready(r_dist.x)
            t_dist = time.perf_counter() - t0
            iters = max(int(r_dist.iters), 1)
            records.append({
                "kind": "dist", "matrix": name, "n": m.n, "nnz": m.nnz,
                "n_dev": n_dev, "format": sop.format,
                "halo_words": int(plan.halo_words),
                "buffer_words": int(plan.buffer_words),
                "allgather_words": int(plan.allgather_words),
                "halo_vs_allgather": plan.halo_words
                / max(plan.allgather_words, 1),
                "has_push": bool(plan.has_push),
                "coll_bytes_halo": coll_halo,
                "coll_bytes_allgather": coll_leg,
                "coll_ratio": (coll_halo / max(coll_leg, 1)
                               if coll_leg is not None else None),
                "iters": int(r_dist.iters),
                "residual_dist": float(r_dist.residual),
                "residual_local": float(r_loc.residual),
                "solve_seconds_dist": t_dist,
                "solve_seconds_local": t_loc,
                "seconds_per_iter_dist": t_dist / iters,
                "seconds_per_iter_local": t_loc / max(int(r_loc.iters), 1),
            })
    return records


def main(quick: bool = False) -> list:
    import jax

    matrices = QUICK_MATRICES if quick else DEFAULT_MATRICES
    n_devs = tuple(d for d in (QUICK_NDEV if quick else DEFAULT_NDEV)
                   if d <= len(jax.devices()))
    if not n_devs:
        raise RuntimeError(
            f"dist_halo needs at least {min(QUICK_NDEV)} devices, found "
            f"{len(jax.devices())}; on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8")
    records = _measure(matrices, n_devs, 40 if quick else 120)
    from .emit_util import emit_kv

    for r in records:
        cr = (f"{r['coll_ratio']:.3f}" if r["coll_ratio"] is not None
              else "n/a")
        emit_kv(f"dist/{r['matrix']}/ndev{r['n_dev']}",
                f"halo_words={r['halo_words']};"
                f"allgather_words={r['allgather_words']};"
                f"ratio={r['halo_vs_allgather']:.3f};"
                f"coll_ratio={cr};"
                f"res={r['residual_dist']:.2e}",
                us=r["seconds_per_iter_dist"] * 1e6)
    return records


if __name__ == "__main__":
    main()
