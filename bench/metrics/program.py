"""Shared reading of the program's own table of spans and compile events,
``repro.core.counters.timings()``: the seconds the process spent in each up
to the reading.  A program without the table, or a table without the name,
reads None."""


def seconds(name: str):
    from repro.core import counters

    table = getattr(counters, "timings", None)
    entry = table().get(name) if table is not None else None
    return None if entry is None else entry["seconds"]
