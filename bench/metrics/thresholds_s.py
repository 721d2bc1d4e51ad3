"""Seconds of the threshold estimate at build time
(``SuperviseResult.timings["thresholds_s"]``, ``core.thresholds``)."""


def read(ctx):
    return ctx["thresholds_s"]
