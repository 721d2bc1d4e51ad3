"""Device idle time per window step while the host was inside a step
dispatch, in ms: the idle gaps of each chip's trace that overlap a
``supervise.ref_dispatch`` or ``supervise.cand_dispatch`` span
(``repro.obs``) inside the window, averaged over the chips used.  A
dispatch that waits on the device (for memory, say) shows here."""

DISPATCH = ("supervise.ref_dispatch", "supervise.cand_dispatch")


def _overlap(a: list, b: list) -> float:
    """Seconds shared by two sorted lists of disjoint intervals."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def read(ctx):
    red = ctx["trace"]
    w0, w1 = red.window
    disp = sorted((max(e.start, w0), min(e.end, w1)) for e in red.host
                  if e.name in DISPATCH and e.end > w0 and e.start < w1)
    used = [d for d, b in red.busy.items() if b > 0]
    if not disp or not used:
        return None
    idle = sum(_overlap(sorted((s, e) for s, e, _ in red.gaps[d]), disp)
               for d in used)
    return 1e3 * idle / len(used) / ctx["steps"]
