"""Readings of the control and of the planted faults, from which the
limits in ``bench/workloads/<cell>.json`` were set.  The benchmark's own
runs never run this.

    python3 bench/controls.py --workload <cell> --seeds 1,2,3 \\
        --modes control,half_batch,token,window_unchanged --seconds 10

Each mode puts something in the program's place and prints, per seed, one
JSON line with the numbers ``correct`` compares:

* ``control``: the plain reference computed at the next precision below
  the configuration's (XLA's HIGH, three bf16 passes; written out as
  ``einsum_3pass`` where XLA ignores the precision), against the
  reference at ``highest``;
* ``half_batch``: the reference with the loss taken over half of the
  batch's rows (over the first half of the tokens where the batch has one
  row), the mean over the rest;
* ``token``: the reference fed one token altered, at a position drawn
  from the seed;
* ``window_unchanged``: the reference with every step from the window's
  first on returning its state unchanged.

Each runs as many steps as a run of ``--seconds`` does.  A step that
returns its state unchanged from the first step on reads ``change_gap``
= 1 by the rule of ``checks.norm_gap`` and needs no run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cell  # noqa: E402
import load  # noqa: E402
from traffic import Batches  # noqa: E402


def half_batch(tokens, labels):
    import numpy as np
    mask = np.zeros(labels.shape, np.float32)
    if labels.shape[0] > 1:
        mask[: labels.shape[0] // 2] = 1.0
    else:
        mask[:, : labels.shape[1] // 2] = 1.0
    return tokens, labels, mask


def token_altered(seed: int, vocab: int):
    import numpy as np
    rng = np.random.default_rng([seed, 7])

    def alter(tokens, labels):
        t = tokens.copy()
        i, j = rng.integers(t.shape[0]), rng.integers(t.shape[1])
        t[i, j] = (t[i, j] + 1) % vocab
        return t, labels, None
    return alter


def reference_readings(workload: str, seed: int, modes: list,
                       seconds: float, overrides=None) -> dict:
    """``{mode: numbers}``: the numbers of ``cell.compare`` with each mode
    in the program's place against the reference at ``highest``, over as
    many steps as a run of ``seconds``.  ``overrides`` (tests) replaces
    parts of the cell (``load.override``)."""
    import jax
    w = load.override(load.cell(workload), overrides)
    c, tf = w["config"]["config"], w["traffic"]
    ref_mod = load.reference(w["config"]["reference"])
    batches = Batches(tf, c["vocab_size"], seed)
    key = cell.seed_key(seed)
    o = tf["optimizer"]
    n_warm, n = cell.window_plan(w, seconds)
    hi = jax.lax.Precision.HIGHEST
    run = partial(cell.reference_run, ref_mod, c, key, batches, o,
                  steps=n_warm + n)
    sides = {
        # XLA's HIGH is three bf16 passes on the TPU; elsewhere XLA ignores
        # the precision, so the same three passes are written out
        "control": lambda: run(jax.lax.Precision.HIGH
                               if jax.default_backend() == "tpu"
                               else "3pass"),
        "half_batch": lambda: run(hi, alter=half_batch),
        "token": lambda: run(hi, alter=token_altered(seed,
                                                     c["vocab_size"])),
        "window_unchanged": lambda: run(hi, freeze_from=n_warm),
    }
    unknown = set(modes) - set(sides)
    if unknown:
        raise ValueError(f"unknown modes {sorted(unknown)}")
    ref = run(hi)
    return {m: cell.compare([sides[m]()], ref, (n_warm, n_warm + n - 1))
            for m in modes}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes",
                    default="control,half_batch,token,window_unchanged")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("controls: no TPU", file=sys.stderr)
        return 2
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        got = reference_readings(args.workload, seed,
                                 args.modes.split(","), args.seconds)
        for mode, nums in got.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "mode": mode, "numbers": nums,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
