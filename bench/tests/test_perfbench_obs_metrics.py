"""The readers of the program's spans and counters (``loop_host_ms``,
``idle_in_dispatch_ms``, ``trace_mb``, ``ckpt_write_s``) on a hand-built
trace reduction and counter table, and the roles ``cell.programs()``
gives the two named step programs."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import cell  # noqa: E402
import load  # noqa: E402
from trace_reduce import Event, Reduced  # noqa: E402

DEV = "/device:TPU:0"


def _step(k: float, ref: tuple, cand: tuple, end: float) -> list:
    """A ``supervise.step`` span from ``k`` to ``end`` holding its two
    dispatch spans."""
    return [Event("supervise.step", k, end),
            Event("supervise.ref_dispatch", *ref),
            Event("bench.cand_step", *cand),
            Event("supervise.cand_dispatch", *cand)]


def _reduced() -> Reduced:
    """A window from 1.0 to 2.0 s with three steps: the first started
    0.1 s before the window opened, the last ends inside it."""
    red = Reduced(window=(1.0, 2.0), devices=[DEV])
    red.host = (_step(0.9, (1.05, 1.10), (1.10, 1.20), 1.3)
                + _step(1.3, (1.35, 1.40), (1.40, 1.60), 1.7)
                + _step(1.7, (1.71, 1.75), (1.75, 1.80), 1.9)
                + [Event("supervise.drain", 1.9, 2.0)])
    red.busy = {DEV: 0.8}
    # idle: 0.02 s inside the second step's candidate dispatch, 0.05 s
    # straddling the third step's reference dispatch (0.03 s inside it),
    # 0.1 s in the drain, and a gap on a chip that ran nothing is left out
    red.gaps = {DEV: [(1.45, 1.47, "supervise.cand_dispatch"),
                      (1.68, 1.73, "supervise.ref_dispatch"),
                      (1.9, 2.0, "supervise.drain")],
                "/device:TPU:1": [(1.0, 2.0, "no host span")]}
    return red


def test_loop_host_ms_counts_only_the_window():
    got = load.metric("loop_host_ms").read({"trace": _reduced(),
                                            "steps": 3})
    # in-window step time less its dispatches: (0.3 - 0.15) + (0.4 - 0.25)
    # + (0.2 - 0.09), averaged over the three step spans
    assert got == pytest.approx(1e3 * (0.15 + 0.15 + 0.11) / 3)


def test_loop_host_ms_without_step_spans_is_none():
    red = _reduced()
    red.host = [e for e in red.host if e.name != "supervise.step"]
    assert load.metric("loop_host_ms").read({"trace": red,
                                             "steps": 3}) is None


def test_idle_in_dispatch_ms_is_idle_under_a_dispatch():
    got = load.metric("idle_in_dispatch_ms").read({"trace": _reduced(),
                                                   "steps": 3})
    assert got == pytest.approx(1e3 * (0.02 + 0.02) / 3)


def test_idle_in_dispatch_ms_counts_a_dispatch_only_inside_the_window():
    red = _reduced()
    red.window = (1.42, 2.0)
    red.gaps[DEV].insert(0, (1.06, 1.08, "supervise.ref_dispatch"))
    got = load.metric("idle_in_dispatch_ms").read({"trace": red,
                                                   "steps": 2})
    assert got == pytest.approx(1e3 * 0.04 / 2)


TABLE = {"spans": {"ckpt.write": {"count": 2, "total_s": 5.0,
                                  "max_s": 3.0}},
         "counts": {"ring.puts": 4, "ring.trace_bytes": 4 * 2_140_000_000},
         "highs": {}}


@pytest.fixture
def table(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "table", lambda: TABLE)


def test_trace_mb_is_bytes_per_put(table):
    assert load.metric("trace_mb").read({}) == pytest.approx(2140.0)


def test_ckpt_write_s_is_the_mean_write(table):
    assert load.metric("ckpt_write_s").read({}) == pytest.approx(2.5)


@pytest.mark.parametrize("name", ["trace_mb", "ckpt_write_s"])
def test_counter_readers_without_the_table_are_none(monkeypatch, name):
    from repro import obs
    monkeypatch.setattr(obs, "table", lambda: {"spans": {}, "counts": {},
                                               "highs": {}})
    assert load.metric(name).read({}) is None
    # a program without ``repro.obs`` (an older checkout)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert load.metric(name).read({}) is None


def test_programs_gives_the_named_steps_their_roles():
    red = Reduced(devices=[DEV])
    ref, cand = "jit_ref_step(123)", "jit_cand_step(456)"
    red.modules[DEV] = [Event(ref, 0.0, 0.05), Event(cand, 0.05, 0.11),
                        Event("jit_relerr_fused(7)", 0.11, 0.12),
                        Event(ref, 0.12, 0.17), Event(cand, 0.17, 0.23)]
    got = cell.programs(red)
    assert got == {"ref_step": pytest.approx(0.10),
                   "cand_step": pytest.approx(0.12)}
