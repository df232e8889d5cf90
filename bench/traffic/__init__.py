"""Traffic: data files ``<traffic>.json`` of parameters, and one driver
module per ``kind`` that they name (``solve.py``, ``apply.py``).

A driver has ``SPAN`` (its host span's name), ``call(op, item, traffic) ->
(output, info)``, ``tally(infos, traffic) -> (iters, failed)``, ``checks(a64, pairs,
traffic) -> {name: value}`` over ``(input, output)`` host pairs, and
``end_to_end(window_s, times, infos, size) -> {metric: value}`` and
``reference(a, item, traffic, rnd) -> output``, the plain reference's
answer with every stored vector rounded by ``rnd`` (the control).
"""
