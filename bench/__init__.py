"""The chip benchmark of the EHYB SpMV framework.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json``.  Everything a cell is made of
is found by name: ``configs/<config>.json`` (the matrix), ``gen/<generator>.py``
(builds it), ``traffic/<traffic>.json`` (the stream of right-hand sides),
``traffic/<kind>.py`` (drives it), ``workloads/<cell>.json`` (the checks'
limits, the control and the CPU rehearsal's cut) and
``metrics/<metric>.py`` (one reader per per-layer metric).
"""
