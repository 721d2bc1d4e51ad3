"""One run of a cell at a size a CPU test can hold, with the chip check
skipped and, optionally, the timed path broken underneath:

    JAX_PLATFORMS=cpu python bench/tests/tiny.py <case>

``case`` is ``clean`` or a fault: ``unchanged_state`` (the candidate's
step returns its state unchanged), ``window_unchanged_state`` (the same
from the window's first step on), ``half_batch`` (it trains on half the
rows, the mean over the rest) or ``token`` (one token altered where the
feed produces it).  A cell on one chip has no exchange between chips to
leave out, and the cells run no check reduction whose answer could be
altered.  Prints the run's result as JSON.
"""
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import cell  # noqa: E402

WORKLOAD = "qwen3-1.7b.unchecked"
TINY = {"config": {"hidden_size": 64, "intermediate_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 16, "vocab_size": 256},
        "traffic": {"batch": 2, "seq": 32}}


def _break_step(sup, broken):
    tap = sup.candidate.step          # the harness's Tap
    inner = tap.step
    tap.step = lambda p, st, b: broken(inner, p, st, b)


def unchanged_state(sup):
    def broken(inner, p, st, b):
        tr, _, _ = inner(p, st, b)
        return tr, p, st
    _break_step(sup, broken)


def window_unchanged_state(sup):
    tap = sup.candidate.step

    def broken(inner, p, st, b):
        tr, p2, st2 = inner(p, st, b)
        return (tr, p2, st2) if tap.bounded else (tr, p, st)
    _break_step(sup, broken)


def half_batch(sup):
    def broken(inner, p, st, b):
        return inner(p, st, {k: v[: v.shape[0] // 2] for k, v in b.items()})
    _break_step(sup, broken)


def token(sup):
    def broken(inner, p, st, b):
        t = b["tokens"].copy()
        t[0, 3] = (t[0, 3] + 1) % TINY["config"]["vocab_size"]
        return inner(p, st, dict(b, tokens=t))
    _break_step(sup, broken)


CASES = {"clean": None, "unchanged_state": unchanged_state,
         "window_unchanged_state": window_unchanged_state,
         "half_batch": half_batch, "token": token}
SECONDS = 10.0      # the window of a run: 98 steps at the cell's step time

if __name__ == "__main__":
    out = cell.run(WORKLOAD, 2 ** 31 + 77, SECONDS, False, t_start=T_START,
                   require_chip=False, overrides=TINY,
                   sabotage=CASES[sys.argv[1]])
    print(json.dumps(out))
