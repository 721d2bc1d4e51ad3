"""Host time of the supervised loop per window step outside the two step
dispatches, in ms: each ``supervise.step`` span on the window's host line
(``repro.obs``) less its ``supervise.ref_dispatch`` and
``supervise.cand_dispatch`` spans, counting only the part inside the
window, averaged over the step spans.  A profiler session records only
the spans that start inside it, so the window's first step, whose batch
opens the window, has no step span."""

STEP = "supervise.step"
DISPATCH = ("supervise.ref_dispatch", "supervise.cand_dispatch")


def read(ctx):
    red = ctx["trace"]
    w0, w1 = red.window
    steps = [e for e in red.host if e.name == STEP]
    if not steps:
        return None
    disp = [e for e in red.host if e.name in DISPATCH]

    def inside(e):
        return max(0.0, min(e.end, w1) - max(e.start, w0))
    host = sum(inside(s) for s in steps) - sum(
        inside(d) for d in disp
        if any(s.start <= d.start and d.end <= s.end for s in steps))
    return 1e3 * host / len(steps)
