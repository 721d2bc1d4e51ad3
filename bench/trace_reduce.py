"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* Device planes are those named ``/device:TPU:<n>``.  Their ``XLA Ops``
  line gives the busy intervals (union of every operation's span); their
  ``XLA Modules`` line gives one event per program execution, with the
  program's name and id.
* Host planes give spans (``TraceAnnotation`` and JAX's own events).  An
  idle gap between two busy intervals of a device is put down to the
  span of the main thread that overlaps it most; of spans that overlap it
  equally, the shortest.

Times are seconds on the profiler's clock, which the device and host
planes share.
"""
from __future__ import annotations

import collections
from dataclasses import dataclass, field


@dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Reduced:
    window: tuple = (0.0, 0.0)          # traced window on the trace's clock
    devices: list = field(default_factory=list)          # plane names
    busy: dict = field(default_factory=dict)     # device -> busy seconds
    modules: dict = field(default_factory=dict)  # device -> [Event]
    ops: dict = field(default_factory=dict)      # device -> [Event]
    gaps: dict = field(default_factory=dict)     # device -> [(s, e, span)]
    first_seen: dict = field(default_factory=dict)  # device -> [program]
    host: list = field(default_factory=list)     # [Event]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        used = [b for b in self.busy.values() if b > 0]
        return sum(used) / len(used) if used else 0.0

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` operations that took most device time, summed over
        the devices and averaged over them: ``[[op, s], ...]``."""
        tot = collections.Counter()
        for evs in self.ops.values():
            for e in evs:
                tot[op_name(e.name)] += e.dur
        k = max(1, len([d for d, b in self.busy.items() if b > 0]))
        return [[name, s / k] for name, s in tot.most_common(n)]

    def top_gaps(self, n: int = 10) -> list:
        """Idle seconds by what the host was doing, averaged over the
        devices: ``[[span, s], ...]``."""
        tot = collections.Counter()
        for gs in self.gaps.values():
            for s, e, span in gs:
                tot[span] += e - s
        k = max(1, len([d for d, b in self.busy.items() if b > 0]))
        return [[name, s / k] for name, s in tot.most_common(n)]


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), calls=...`` -> ``fusion.12``."""
    head = hlo.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(line) -> list:
    out = []
    for ev in line.events:
        s = ev.start_ns * 1e-9
        out.append(Event(ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def load(path: str, span: str = "bench.window") -> Reduced:
    """Reduce the trace at ``path`` over the host span named ``span`` (the
    measured window; the device events' extent where it is absent).  Idle
    gaps are put down to spans of the thread that recorded ``span``.

    The device planes' timestamps run about 2 ms ahead of the host's on a
    v5e (a program's device events start before the host call that
    dispatched it): gaps shorter than that are put down to the host's
    spans only roughly."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    red = Reduced()
    host_lines = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            red.devices.append(plane.name)
            red.modules[plane.name] = _events(lines["XLA Modules"]) \
                if "XLA Modules" in lines else []
            red.ops[plane.name] = _events(lines["XLA Ops"]) \
                if "XLA Ops" in lines else []
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_lines.append([e for e in _events(line) if e.dur > 0])
    red.devices.sort(key=lambda d: int(d.rsplit(":", 1)[-1]))
    for dev in red.devices:             # over the whole trace, unclipped
        red.modules[dev].sort(key=lambda e: e.start)
        red.ops[dev].sort(key=lambda e: e.start)
        red.first_seen[dev] = list(dict.fromkeys(
            e.name for e in red.modules[dev]))
    main = next((evs for evs in host_lines
                 if any(e.name == span for e in evs)), [])
    win = [e for e in main if e.name == span]
    if win:
        w0, w1 = win[0].start, win[0].end
    else:
        spans = [e for evs in red.ops.values() for e in evs]
        w0, w1 = ((min(e.start for e in spans), max(e.end for e in spans))
                  if spans else (0.0, 0.0))
    red.window = (w0, w1)
    clip = lambda evs: [e for e in evs if e.end > w0 and e.start < w1]
    red.host = [e for e in clip(main) if e.name != span]
    for dev in red.devices:
        red.modules[dev] = clip(red.modules[dev])
        red.ops[dev] = clip(red.ops[dev])
        busy = _union([(max(e.start, w0), min(e.end, w1))
                       for e in red.ops[dev]])
        red.busy[dev] = sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        red.gaps[dev] = _blame(gaps, red.host)
    return red


def _blame(gaps: list, host: list) -> list:
    """``[(start, end, span)]``: each gap with the host span that overlaps
    it most (the shortest of equals), by one sweep over both sorted
    lists."""
    host = sorted(host, key=lambda h: h.start)
    out, active, i = [], [], 0
    for s, e in gaps:
        while i < len(host) and host[i].start < e:
            active.append(host[i])
            i += 1
        active = [h for h in active if h.end > s]
        best, key = "no host span", (0.0, 0.0)
        for h in active:
            k = (min(e, h.end) - max(s, h.start), -h.dur)
            if k > key:
                best, key = h.name, k
        out.append((s, e, best))
    return out
