"""Host seconds of ``repro.api.plan`` (partition choice or tune-store
load, format check, host EHYB build)."""


def read(rec):
    return rec["plan_s"]
