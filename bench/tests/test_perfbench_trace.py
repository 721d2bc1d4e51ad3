"""The trace reduction on a trace recorded on a TPU v5e: two programs
(``matmul_a``, ``reduce_b``) called three times each, with a host span
around each call and a 10 ms host sleep between them.  The expected
numbers were read off the file by a separate count: every device
operation's interval painted onto a 1 ns grid, and each program's events
summed by hand."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402

FIXTURE = os.path.join(BENCH, "tests", "fixtures",
                       "v5e_two_programs.xplane.pb")
DEV = "/device:TPU:0"


@pytest.fixture(scope="module")
def in_span():
    return trace_reduce.load(FIXTURE, "fixture.window")


def test_window_and_busy_share(in_span):
    assert in_span.devices == [DEV]
    assert in_span.window_s == pytest.approx(36_469_455e-9, abs=1e-9)
    assert in_span.busy[DEV] == pytest.approx(299_131e-9, abs=1e-9)
    assert in_span.busy_s() == in_span.busy[DEV]
    idle = 1 - in_span.busy_s() / in_span.window_s
    assert idle == pytest.approx(0.991798, abs=1e-6)


def test_per_program_device_time(in_span):
    a = [e for e in in_span.modules[DEV] if "jit_matmul_a" in e.name]
    b = [e for e in in_span.modules[DEV] if "jit_reduce_b" in e.name]
    # the device's clock runs ahead of the host's: the first matmul_a
    # starts before the host span that dispatched it, and is left out
    assert len(a) == 2 and len(b) == 3
    assert sum(e.dur for e in a) == pytest.approx((113_957 + 113_932) * 1e-9,
                                                  abs=1e-9)
    assert sum(e.dur for e in b) == pytest.approx(
        (23_728 + 23_756 + 23_777) * 1e-9, abs=1e-9)


def test_idle_gaps_go_to_the_host_span(in_span):
    gaps = in_span.top_gaps(10)
    assert gaps[0][0] == "fixture.sleep"
    assert gaps[0][1] > 0.03          # three 10 ms sleeps
    assert sum(s for _, s in gaps) == pytest.approx(
        in_span.window_s - in_span.busy_s(), abs=1e-9)


def test_ops_are_named_by_their_instruction(in_span):
    names = [n for n, _ in in_span.top_ops(10)]
    assert names[0] == "fusion" and "tanh_reduce_fusion" in names


def test_without_the_span_the_window_is_the_device_extent():
    red = trace_reduce.load(FIXTURE, "no.such.span")
    assert red.window_s == pytest.approx(35_748_445e-9, abs=1e-9)
    assert red.busy[DEV] == pytest.approx(413_193e-9, abs=1e-9)
    assert sum("jit_matmul_a" in e.name for e in red.modules[DEV]) == 3
