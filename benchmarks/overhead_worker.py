"""Worker: detection latency — naive loss-curve watching vs TTrace (§6.4).

The naive practice trains BOTH the single-device reference and the
distributed candidate, watching for a >=3% smoothed-loss gap.  TTrace runs
ONE instrumented iteration.  The injected bug is dp_wrong_loss_scale — the
grads are 2x but gradient clipping mostly hides it, so the curves stay close
for a long time (the paper's Fig 1 blindness).

Prints TSV: metric \t value
"""
import os
from benchmarks.common import cpu_host_devices

cpu_host_devices(os.environ, 8)

import dataclasses
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core.harness import make_model_runner, ttrace_check
from repro.data.synthetic import make_batch
from repro.launch.steps import make_train_step
from repro.models.model import Model
from repro.optim.adamw import AdamW
from repro.parallel.api import (ParallelConfig, make_candidate_runner,
                                make_plain_train_step)

BUG = "dp_wrong_loss_scale"
MAX_STEPS = 300
GAP = 0.03


def main():
    cfg = dataclasses.replace(get_config("gpt-paper").reduced(),
                              n_layers=2, vocab=512, tie_embeddings=True)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=3e-3)
    pc = ParallelConfig(dp=2, tp=2, bugs=frozenset([BUG]))

    # --- naive: train both, watch the loss ---------------------------------
    t0 = time.time()
    ref_step = jax.jit(make_train_step(m, opt))
    rp, rs = params, opt.init(params)
    cstep, prep, cp_, cs_ = make_plain_train_step(cfg, pc, params, opt)
    ref_hist, cand_hist = [], []
    detect_step = None
    for step in range(MAX_STEPS):
        batch = make_batch(cfg, 4, 32, step=step)
        rp, rs, met = ref_step(rp, rs, batch)
        ref_hist.append(float(met["loss"]))
        cp_, cs_, closs = cstep(cp_, cs_, prep(batch))
        cand_hist.append(float(closs))
        if step >= 20:
            r = np.mean(ref_hist[-20:])
            c = np.mean(cand_hist[-20:])
            if abs(c - r) / max(r, 1e-9) > GAP and detect_step is None:
                detect_step = step
                break
    t_naive = time.time() - t0

    # --- ttrace: one instrumented iteration --------------------------------
    t0 = time.time()
    ref = make_model_runner(m, params, opt, opt.init(params))
    cand = make_candidate_runner(cfg, pc, params, opt, opt.init(params))
    res = ttrace_check(ref, cand, make_batch(cfg, 4, 32), localize=True)
    t_ttrace = time.time() - t0

    print(f"naive_detect_step\t{detect_step if detect_step is not None else f'>{MAX_STEPS}'}")
    print(f"naive_seconds\t{t_naive:.1f}")
    print(f"ttrace_detected\t{not res.passed}")
    print(f"ttrace_localized\t{res.localized_module}")
    print(f"ttrace_seconds\t{t_ttrace:.1f}")
    print(f"speedup\t{t_naive / max(t_ttrace, 1e-9):.1f}")
    print(f"loss_gap_final\t{abs(np.mean(cand_hist[-20:]) - np.mean(ref_hist[-20:])) / np.mean(ref_hist[-20:]):.4f}")


if __name__ == "__main__":
    main()
