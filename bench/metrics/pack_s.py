"""Host seconds of packing inside ``Plan.bind`` (the program's
``repro.bind.pack`` span: ``pack_staircase`` and
``group_er_by_partition``), before the upload."""

from bench.metrics import program


def read(rec):
    return program.seconds("repro.bind.pack")
