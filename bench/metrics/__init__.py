"""Per-layer metric readers, one file per metric of ``BENCHMARK.json``.

Each has ``read(rec) -> float | None``.  ``rec`` holds the run's host
set-up times (``plan_s``, ``bind_s``), the window's calls (``ops``) and
solver iterations (``iters``), the matrix size (``n``, ``nnz``, ``k``,
``dtype``), ``device_kind``, and ``trace``: the :func:`bench.trace_reduce.reduce`
summary of the traced window (None without a trace).  A reader that finds
nothing to read returns None, and the harness leaves its metric out.
"""
