"""The program's own spans and counters.

``span(name, **args)`` is a context manager: it opens a
``jax.profiler.TraceAnnotation``, so the span lands on a profiler trace's
host timeline (on the device trace's clock) whenever a profiler session
runs, and it adds its ``perf_counter`` duration to a process-wide table.
``count(name, n)`` adds to a counter there, ``high(name, v)`` keeps a
high-water mark.  Every call is thread-safe: the writer threads record
into the same table as the loop.

``table()`` is a copy of the table; ``since(before)`` is its change since
an earlier copy (``SuperviseResult.obs``).  A maximum cannot be taken
apart, so span maxima and high-water marks in a change are the process's.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

_lock = threading.Lock()
_spans: dict[str, list] = {}        # name -> [count, total_s, max_s]
_counts: dict[str, float] = {}
_highs: dict[str, float] = {}


class span:
    """``with span("supervise.step", step=k) as s:`` ... ``s.seconds``."""
    __slots__ = ("name", "seconds", "_ann", "_t0")

    def __init__(self, name: str, **args):
        self.name, self.seconds = name, 0.0
        self._ann = TraceAnnotation(name, **args)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        with _lock:
            e = _spans.get(self.name)
            if e is None:
                _spans[self.name] = [1, dt, dt]
            else:
                e[0] += 1
                e[1] += dt
                e[2] = max(e[2], dt)
        return False


def count(name: str, n: float = 1) -> None:
    with _lock:
        _counts[name] = _counts.get(name, 0) + n


def high(name: str, v: float) -> None:
    with _lock:
        if v > _highs.get(name, float("-inf")):
            _highs[name] = v


def table() -> dict:
    """``{"spans": {name: {"count", "total_s", "max_s"}}, "counts":
    {name: n}, "highs": {name: v}}``."""
    with _lock:
        return {"spans": {k: {"count": c, "total_s": t, "max_s": m}
                          for k, (c, t, m) in _spans.items()},
                "counts": dict(_counts), "highs": dict(_highs)}


def since(before: dict) -> dict:
    """The change in ``table()`` since ``before``."""
    now, spans = table(), {}
    for k, s in now["spans"].items():
        b = before["spans"].get(k, {"count": 0, "total_s": 0.0})
        if s["count"] > b["count"]:
            spans[k] = {"count": s["count"] - b["count"],
                        "total_s": s["total_s"] - b["total_s"],
                        "max_s": s["max_s"]}
    counts = {k: v - before["counts"].get(k, 0)
              for k, v in now["counts"].items()
              if v != before["counts"].get(k, 0)}
    return {"spans": spans, "counts": counts, "highs": now["highs"]}


def report(t: dict) -> str:
    """One line per span (longest total first), then per counter and
    high-water mark."""
    lines = [f"  {k:<38} {s['count']:>7}x  total {s['total_s']:10.4f} s  "
             f"max {s['max_s']:.4f} s"
             for k, s in sorted(t["spans"].items(),
                                key=lambda kv: -kv[1]["total_s"])]
    lines += [f"  {k:<38} {v:>15,.0f}"
              for k, v in sorted(t["counts"].items())]
    lines += [f"  {k:<38} {v:>15,.0f} (high)"
              for k, v in sorted(t["highs"].items())]
    return "\n".join(lines)
