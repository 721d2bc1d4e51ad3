"""Pipeline-parallel staged execution + the stage-division silent bug.

Single-controller JAX gets no correctness surface from a 1F1B microbatch
schedule, but pipeline parallelism's *semantic* content — which stage owns
which layers, and how stage-local layer indices map back to the reference
numbering (paper Fig 5) — is fully modeled here:

* ``stage_division`` computes each stage's [start, end) global layer range,
  distributing any remainder one-per-stage from the front (Megatron-style
  uneven PP) so every layer runs exactly once for ANY (L, pp); with
  ``pp_wrong_stage_division`` injected, boundaries are computed with a
  rounded layers-per-stage (the classic ``ceil(L/pp)`` bug): one layer is
  executed twice at a stage boundary and another never runs — silent, loss
  still decreases, the model is simply wrong (paper bug 10).
* ``stage_layer_table`` precomputes, once, the (executed layer, canonical
  name index) pairs in execution order — the STAGE-LOCAL → global renaming
  (``canonical_layer_index``) that both the one-shot runner and the
  supervisor's once-compiled train step bake into their traced loss, so the
  mapping is preserved bit-for-bit across supervised steps.
* ``make_pp_runner`` executes the model stage by stage with stage-local
  numbering and canonical tap names aligned with the single-device
  reference; ``make_pp_train_step`` is the once-jitted stateful FULL train
  step (the supervisor's ``CandidateStep`` contract for ``--recipe pp``).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.collector import Trace, make_trace_collector, make_trace_step
from repro.core.tap import ensure_ctx
from repro.models.model import Model, block_apply


def stage_division(n_layers: int, pp_size: int,
                   bugs=frozenset()) -> list[tuple[int, int]]:
    if "pp_wrong_stage_division" in bugs:
        # W-CP: ceil-based boundaries overlap by one layer per boundary and
        # drop the tail — stage i executes [i*cpl_bad, ...) with
        # cpl_bad = ceil(L/pp) clipped at L, so a layer repeats and the last
        # layer(s) never run.
        cpl = math.ceil(n_layers / pp_size) if pp_size > 1 else n_layers
        out = []
        for r in range(pp_size):
            start = min(r * cpl - (1 if r else 0), n_layers)
            end = min(start + cpl, n_layers)
            out.append((start, end))
        return out
    # exact partition: base layers per stage, remainder distributed
    # one-per-stage from the front (Megatron uneven pipeline division) —
    # floor alone would silently drop the last L % pp layers
    base, rem = divmod(n_layers, pp_size)
    out, start = [], 0
    for r in range(pp_size):
        end = start + base + (1 if r < rem else 0)
        out.append((start, end))
        start = end
    return out


def stage_layer_table(n_layers: int, pp_size: int,
                      bugs=frozenset()) -> list[tuple[int, int]]:
    """Static ``(executed_layer, canonical_index)`` pairs in execution order.

    The canonical index is reconstructed from (pp_rank, local index) under
    the CORRECT division — exactly the renaming a per-rank trace would apply
    (paper Fig 5; for divisible layer counts it coincides with
    ``core.canonical.canonical_layer_index``) — so when the injected bug
    shifts the executed ranges the names stay put and the trace misaligns
    with the reference.  Buggy
    overlapping stages can claim an already-used canonical index on uneven
    divisions; those spill to fresh indices >= L (absent from the reference,
    reported as extra candidate tensors) instead of colliding in one trace.
    """
    stages = stage_division(n_layers, pp_size, bugs)
    correct = stage_division(n_layers, pp_size)
    table, used, overflow = [], set(), n_layers
    for pp_rank, (start, end) in enumerate(stages):
        for local_idx in range(end - start):
            # the correct stage's offset + local index; for divisible L this
            # equals canonical_layer_index(local_idx, pp_rank, pp_size, 0, 1)
            # (asserted by the property tests against core.canonical)
            canon = correct[pp_rank][0] + local_idx
            if canon in used:
                canon, overflow = overflow, overflow + 1
            used.add(canon)
            table.append((start + local_idx, canon))
    return table


def _pp_loss_call(model: Model, pp_size: int, bugs=frozenset()):
    """``loss_call(params, batch, ctx)`` for the stage-partitioned candidate
    with canonical (global) tap names baked in — shared by the one-shot
    runner and the once-compiled supervised step."""
    cfg = model.cfg
    table = stage_layer_table(cfg.n_layers, pp_size, bugs)

    def loss_call(p, batch, ctx):
        ctx = ensure_ctx(ctx)
        h = model.embed(p, batch, ctx)
        from repro.models.layers import rmsnorm
        aux = jnp.zeros((), jnp.float32)
        for executed, canon in table:
            with ctx.scope(f"layers.{canon}"):
                h, a, _ = block_apply(p["layers"][executed], cfg,
                                      "attn_mlp", h, ctx)
            aux = aux + a
        h = rmsnorm(p["final_norm"], h)
        h = ctx.tap("final_norm_out", h)
        e = (p["embedding"]["word_embeddings"] if cfg.tie_embeddings
             else p["lm_head"])
        from repro.models.layers import cross_entropy, _logits
        return cross_entropy(_logits(h, e), batch["labels"]) + aux

    return loss_call


def make_pp_runner(model: Model, params, pp_size: int, opt=None,
                   opt_state=None, bugs=frozenset()):
    """Runner(batch, rewrites) -> Trace for the stage-partitioned candidate.

    Tap names use canonical (global) layer indices reconstructed from
    (pp_rank, local index) — identical to the reference's names when the
    division is correct."""
    collect = make_trace_collector(_pp_loss_call(model, pp_size, bugs), opt)

    def run(batch, rewrites=None) -> Trace:
        tr, _, _ = collect(params, batch, opt_state, rewrites)
        return tr

    return run


def make_pp_train_step(model: Model, ref_params, opt, batch, pp_size: int,
                       bugs=frozenset()):
    """Once-compiled stateful PP candidate train step (supervisor contract).

    Returns ``(step, params0, opt_state0)`` with ``step(params, opt_state,
    batch) -> (Trace, new_params, new_opt_state)`` — one jitted callable,
    the stage-local → canonical tap renaming traced in, reused verbatim
    every supervised step and bisection replay."""
    import jax
    loss_call = _pp_loss_call(model, pp_size, bugs)
    step = make_trace_step(loss_call, opt, ref_params, batch,
                           name="cand_step")
    params0 = jax.tree.map(jnp.asarray, ref_params)
    return step, params0, opt.init(params0)
