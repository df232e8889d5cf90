"""Host seconds of the EHYB host build inside ``repro.api.plan`` (the
program's ``repro.plan.build`` span: partitioning when no partition is
stored, then the metadata and reorder passes of ``build_ehyb``)."""

from bench.metrics import program


def read(rec):
    return program.seconds("repro.plan.build")
