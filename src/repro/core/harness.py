"""TTrace top-level API (paper §3 debugging workflow).

    result = ttrace_check(
        reference=make_model_runner(model, params, batch_opts...),
        candidate=<runner from repro.parallel or any step fn>,
        batch=batch,
        eps=machine epsilon of the recipe,
    )

A *runner* is ``fn(batch, rewrites) -> Trace``.  The harness performs:
  step 1  threshold estimation (reference run + eps-perturbed reference run)
  step 3  candidate run with trace collection
  step 4  differential testing -> Report
  step 5  if flagged: rewrite-mode localization (module-isolated inputs)

Integration cost for a new step function is the runner closure — the
"fewer than 10 lines of code" the paper advertises.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from repro.core.checker import Report, compare_traces, localize_with_rewrites
from repro.core.collector import (Trace, make_trace_collector, model_loss_call,
                                  trace_pair_step)
from repro.core.thresholds import MACHINE_EPS, Thresholds, estimate_thresholds


@dataclass
class TTraceResult:
    report: Report                      # step-4 differential report
    localization: Optional[Report]      # step-5 rewrite-mode report (if run)
    thresholds: Thresholds
    reference: Trace
    candidate: Trace

    @property
    def passed(self) -> bool:
        return self.report.passed

    @property
    def localized_module(self) -> Optional[str]:
        if self.localization is not None and self.localization.localized:
            return self.localization.localized
        return self.report.localized

    def summary(self) -> str:
        s = self.report.summary()
        if self.localization is not None:
            s += "\n--- rewrite-mode localization ---\n"
            s += self.localization.summary()
        return s


def make_model_runner(model, params, opt=None, opt_state=None,
                      tap_filter=None, jit=True) -> Callable:
    """Reference runner over the single-device model zoo.

    The runner builds its trace program on its first call and reuses it for
    every batch of that shape: one program for plain runs, one for each set
    of rewritten taps (the threshold estimate's perturbed run).

    The returned runner also exposes ``run.pair(batch2) -> (Trace, Trace)``
    — two batches stacked on a leading axis collected in ONE vmapped
    compiled call — which threshold estimation uses to fuse the base and
    eps-perturbed reference runs for float-input models.
    """
    collect = make_trace_collector(model_loss_call(model), opt,
                                   tap_filter=tap_filter, jit=jit)

    def run(batch, rewrites=None) -> Trace:
        tr, _, _ = collect(params, batch, opt_state, rewrites)
        return tr

    def run_pair(batch2):
        return trace_pair_step(model, params, batch2, opt=opt,
                               opt_state=opt_state, tap_filter=tap_filter,
                               jit=jit)

    run.pair = run_pair
    return run


def make_decode_runner(model, params, decode_fn=None, taps_every: int = 1):
    """Inference-mode runner (paper §7 'extension to inference', implemented
    here): steps the decode path over the prompt, tapping each step's logits
    and the final cache leaves.  ``decode_fn(params, cache, tokens, pos)``
    defaults to ``model.decode_step``; pass an alternative implementation
    (e.g. naive vs absorbed MLA decode) as the candidate."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.collector import Trace, flatten_named

    fn = decode_fn or model.decode_step
    fn = jax.jit(fn)

    def run(batch, rewrites=None) -> Trace:
        toks = jnp.asarray(batch["tokens"])
        B, T = toks.shape
        cache = model.init_cache(B, T)
        tr = Trace()
        for t in range(T):
            logits, cache = fn(params, cache, toks[:, t:t + 1], jnp.int32(t))
            if t % taps_every == 0:
                tr.activations[f"decode.t{t}/logits"] = np.asarray(
                    logits, np.float32)
        for name, leaf in flatten_named(cache).items():
            tr.activations[f"decode.final_cache.{name}/value"] =                 np.asarray(leaf, np.float32)
        tr.meta["fwd_order"] = list(tr.activations)
        tr.loss = float(np.mean(tr.activations[f"decode.t{T-1}/logits"]))
        return tr

    return run


def ttrace_supervise(model, cfg, pcfg, opt, params=None, steps: int = 8,
                     batch_fn: Optional[Callable] = None, **kwargs):
    """Multi-step analogue of ``ttrace_check``: run reference and candidate
    training loops in lockstep for ``steps`` steps with online (async)
    checking, and on a flag bisect to the first bad step and localize.

    Recipe-generic: ``pcfg`` selects the shard_map (dense/MoE/ZeRO-1),
    staged pipeline (``pp=N``), real multi-device 1F1B pipeline
    (``pp=N, pp_schedule="1f1b", microbatches=M`` — per-rank traces merged
    before checking) or FP8 (``fp8="tile128"`` etc., checked under BF16
    epsilon automatically) candidate.

    Thin facade over ``repro.supervise.Supervisor`` — ``kwargs`` map onto
    ``SuperviseConfig`` fields (``check_every``, ``async_window``,
    ``ckpt_every``, ``reestimate_every``, ...) plus
    ``batch_size``/``seq_len``/``log_fn`` for the default synthetic batch
    stream.  Returns a ``SuperviseResult`` whose
    ``summary()``/``passed``/``localized_module`` mirror ``TTraceResult``.
    """
    from repro.supervise import Supervisor, SuperviseConfig
    sup_kw = {k: kwargs.pop(k) for k in ("batch_size", "seq_len", "log_fn")
              if k in kwargs}
    scfg = SuperviseConfig(steps=steps, **kwargs)
    return Supervisor(model, cfg, pcfg, opt, params=params, scfg=scfg,
                      batch_fn=batch_fn, **sup_kw).run()


def ttrace_check(reference: Callable, candidate: Callable, batch: dict,
                 eps: float = MACHINE_EPS["float32"], margin: float = 8.0,
                 localize: bool = True, seed: int = 0,
                 estimate: bool = True) -> TTraceResult:
    if not estimate:
        # floor-only thresholds (decode runners have integer inputs and no
        # rewrite surface; margin * floor_mult * eps per tensor)
        thr = Thresholds(eps=eps, margin=margin)
        ref_trace = reference(batch, None)
    else:
        thr, ref_trace = estimate_thresholds(reference, batch, eps, margin,
                                             seed)
    cand_trace = candidate(batch, None)
    report = compare_traces(ref_trace, cand_trace, thr)
    loc = None
    if localize and not report.passed:
        loc = localize_with_rewrites(reference, candidate, batch, ref_trace,
                                     thr)
    return TTraceResult(report=report, localization=loc, thresholds=thr,
                        reference=ref_trace, candidate=cand_trace)
