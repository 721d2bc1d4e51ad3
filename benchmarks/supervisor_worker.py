"""Worker (8 forced host devices): supervisor overhead vs an unsupervised
training loop.

Four loops over the same (arch, parallelism, batch stream):

* ``plain``   — the bare distributed candidate train step: no tracing, no
  reference, no checking (what production training costs);
* ``nocheck`` — the supervisor's lockstep loop with checking off: reference
  + candidate traced steps, no differential checks (the "unsupervised
  loop" the overhead criterion compares against — training both sides is
  the floor the checking policy sits on);
* ``sync``    — supervised run with ``async_window=0``: every step blocks
  on its own differential check before the next step dispatches;
* ``async``   — supervised run with a 2-deep in-flight check window (the
  double-buffered pipeline).

Prints ``key\tvalue`` TSV of steady-state (post-compilation) seconds/step.
Spill is disabled for all timed runs so the rows compare checking policies,
not disk bandwidth; a fourth row times the default spill-enabled ring for
reference.
"""
import os

from benchmarks.common import cpu_host_devices

cpu_host_devices(os.environ, 8)

import dataclasses
import time

import jax

from repro.configs.base import get_config
from repro.data.synthetic import make_batch
from repro.models.model import Model
from repro.optim.adamw import AdamW
from repro.parallel.api import ParallelConfig, make_plain_train_step
from repro.supervise import Supervisor, SuperviseConfig

# 24 steady steps: single-shot rows on the 2-core container swing ~20%
# between runs at 18 steps; the longer window tames the ratio rows.
# On top of that every row is best-of-TRIALS (min): the first trial pays
# compilation, later trials hit the jit caches and cost only the steady
# steps, so the repeat is nearly free and strips co-tenant noise spikes
# that single-shot rows keep tripping the acceptance ratios on
STEPS = 3 if os.environ.get("REPRO_BENCH_SMOKE") else 24
TRIALS = 1 if os.environ.get("REPRO_BENCH_SMOKE") else 2
WARM = 2
BATCH, SEQ = 4, 32


def main():
    cfg = dataclasses.replace(get_config("gpt-paper").reduced(),
                              n_layers=2, vocab=512, tie_embeddings=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pcfg = ParallelConfig(dp=2, tp=2)

    # --- unsupervised plain candidate loop ---------------------------------
    opt = AdamW(lr=1e-3)
    step_fn, prep, p, s = make_plain_train_step(cfg, pcfg, params, opt)
    loss = None
    for k in range(WARM):
        p, s, loss = step_fn(p, s, prep(make_batch(cfg, BATCH, SEQ, step=k)))
    loss.block_until_ready()
    plain = float("inf")
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        for k in range(WARM, WARM + STEPS):
            p, s, loss = step_fn(p, s,
                                 prep(make_batch(cfg, BATCH, SEQ, step=k)))
        loss.block_until_ready()
        plain = min(plain, (time.perf_counter() - t0) / STEPS)
    print(f"plain_s_per_step\t{plain:.6f}")

    # --- supervised runs ----------------------------------------------------
    def supervised(window: int, spill: bool, check_every: int = 1,
                   run_pcfg: ParallelConfig = pcfg,
                   reestimate_every: int = 0, journal: bool = False):
        # journal=False for the legacy rows: they time checking policies;
        # the fsync'd journal is priced by its own dedicated row
        best = float("inf")
        for _ in range(TRIALS):
            sup = Supervisor(
                model, cfg, run_pcfg, AdamW(lr=1e-3), params=params,
                scfg=SuperviseConfig(steps=WARM + STEPS,
                                     async_window=window,
                                     check_every=check_every,
                                     reestimate_every=reestimate_every,
                                     spill=spill, ring_window=4,
                                     ckpt_every=WARM + STEPS,
                                     stop_on_flag=False, journal=journal),
                batch_size=BATCH, seq_len=SEQ)
            res = sup.run()
            assert res.passed, ("clean supervised run flagged:\n"
                                + res.summary())
            best = min(best, 1.0 / res.timings["steady_steps_per_s"])
        return best

    # checking off entirely (check_every=0): the bare lockstep loop.  The
    # old form (check_every > run length) was the bench-harness bug behind
    # the "nocheck slower than async2" anomaly: the ring window scales with
    # check_every to honor the pin contract, so EVERY trace of the run
    # stayed live and the loop paid allocator pressure checking never pays
    nocheck = supervised(window=2, spill=False, check_every=0)
    print(f"nocheck_s_per_step\t{nocheck:.6f}")
    sync_s = supervised(window=0, spill=False)
    print(f"sync_s_per_step\t{sync_s:.6f}")
    async_s = supervised(window=2, spill=False)
    print(f"async_s_per_step\t{async_s:.6f}")
    # the fault-tolerance tax: same async loop with the fsync'd per-step
    # journal on (one step + one verdict record per step at this cadence)
    journal_s = supervised(window=2, spill=False, journal=True)
    print(f"journal_s_per_step\t{journal_s:.6f}")
    print(f"journal_overhead_x\t{journal_s / async_s:.3f}")
    spill_s = supervised(window=2, spill=True)
    print(f"async_spill_s_per_step\t{spill_s:.6f}")
    print(f"async_overhead_x\t{async_s / nocheck:.3f}")
    print(f"sync_overhead_x\t{sync_s / nocheck:.3f}")

    # --- recipe-generic supervision: pp / fp8 candidates --------------------
    pp_s = supervised(window=2, spill=False,
                      run_pcfg=ParallelConfig(pp=2))
    print(f"pp_s_per_step\t{pp_s:.6f}")
    # real multi-device 1F1B engine: 2 stages on 2 devices, 2 microbatches,
    # per-rank traces merged before every online check
    pp1f1b_s = supervised(window=2, spill=False,
                          run_pcfg=ParallelConfig(pp=2, pp_schedule="1f1b",
                                                  microbatches=2))
    print(f"pp1f1b_s_per_step\t{pp1f1b_s:.6f}")
    fp8_s = supervised(window=2, spill=False,
                       run_pcfg=ParallelConfig(fp8="tile128"))
    print(f"fp8_s_per_step\t{fp8_s:.6f}")
    # periodic re-estimation overhead on the async dense loop (R = 1/3 run)
    reest_s = supervised(window=2, spill=False,
                         reestimate_every=(WARM + STEPS) // 3)
    print(f"reest_s_per_step\t{reest_s:.6f}")


if __name__ == "__main__":
    main()
