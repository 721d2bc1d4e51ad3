"""Bytes of both sides' traces per supervised step, in MB (1e6 B): the
program's counters ``ring.trace_bytes`` over ``ring.puts``
(``repro.obs``), counted from the trace sections' shapes.  None where the
program keeps no such counters."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.table()["counts"]
    if not c.get("ring.puts"):
        return None
    return c["ring.trace_bytes"] / c["ring.puts"] / 1e6
