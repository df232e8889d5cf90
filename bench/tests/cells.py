"""The benchmark's cells cut to a size a CPU test run can hold.

Only the grid shrinks: the generator, the values, the format, the traffic
and the limits stay those of ``BENCHMARK.json``.
"""

import time

from bench import harness

SMALL = {"hpcg.cg": {"grid": [6, 6, 6]},
         "elast68.spmv": {"node_grid": [4, 4, 4]}}
CELLS = sorted(SMALL)


def small_spec(cell: str) -> dict:
    spec = harness.load_cell(cell)
    spec["config"].update(SMALL[cell])
    return spec


def run_small(cell: str, store, *, seeds=(3,), seconds=0.3, trace=False,
              values=None) -> list:
    """Every result of one small run of ``cell`` on this host's JAX."""
    from repro.tuning.store import clear_store

    try:
        return list(harness.run(small_spec(cell), list(seeds), seconds,
                                trace, t0=time.perf_counter(),
                                values=values, log=lambda msg: None,
                                store=store))
    finally:
        clear_store()
