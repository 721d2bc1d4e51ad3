"""Device time per step of the reference side's jitted training step
(``core.collector.make_trace_step``), in ms."""


def read(ctx):
    s = ctx["programs"].get("ref_step")
    return None if s is None else 1e3 * s / ctx["steps"]
