"""Host seconds of ``Plan.bind`` up to the device tables being ready
(packing, ER grouping, upload)."""


def read(rec):
    return rec["bind_s"]
