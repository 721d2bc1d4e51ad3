"""A whole run of a cell on the CPU at a tiny size, with the harness's
look for a chip skipped: clean, it is correct; with the timed path broken
underneath in each way a one-chip training cell can break, or with the
reference at the next lower precision in the program's place, it is
not."""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)


def run_case(case: str) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "tiny.py"), case],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.dirname(BENCH))
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_clean_run_is_correct():
    out = run_case("clean")
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"supervised_step_s", "peak_hbm_gb",
                                   "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("case", ["unchanged_state", "window_unchanged_state",
                                  "half_batch", "token"])
def test_broken_timed_path_is_not_correct(case):
    out = run_case(case)
    assert not out["correct"], out["checks"]
    if case == "window_unchanged_state":
        # the first three steps are sound; only the window shows it
        got = out["checks"]
        assert got["loss_gap"]["value"] <= got["loss_gap"]["limit"]
        assert got["window_loss_gap"]["value"] > \
            got["window_loss_gap"]["limit"]


def test_control_is_not_correct():
    """The plain reference at three bf16 passes in the program's place, at
    ``qwen2-1.5b``'s published widths with a short sequence and a small
    vocabulary slice: it fails the cell's limits."""
    import controls
    import load
    cell = "qwen2-1.5b.unchecked"
    limits = load.cell(cell)["limits"]
    got = controls.reference_readings(
        cell, 5, ["control"], 0.2, {"config": {"vocab_size": 1024},
                                    "traffic": {"seq": 64}})["control"]
    assert any(got[k] > limits[k] for k in got), (got, limits)
