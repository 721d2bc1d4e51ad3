"""Real multi-device 1F1B pipeline parallelism with per-rank trace merging.

Unlike ``parallel.pp`` — a single-controller *staged* candidate that bakes
the canonical stage-local -> global renaming into one jitted loss — this
engine runs the pipeline the way a PP framework does (paper §5, Fig 5):

* the model is partitioned onto **per-stage single-device submeshes** built
  from the process's (forced-host) device list: stage ``s`` holds only its
  own layer slice, plus the embedding on stage 0 and the final norm /
  LM head on the last stage.  Tied embeddings are replicated on both ends
  and their gradients explicitly reduced across the two stages
  (Megatron-style tied-embedding all-reduce);
* execution follows the **1F1B microbatch schedule** (``stage_op_stream``
  per stage: warmup forwards, steady one-forward-one-backward, cooldown
  backwards) under **dependency-driven per-stage dispatch**: each stage's
  jitted op launches the moment its cross-stage input's device future
  exists — no host clock-tick linearization — with stage-boundary
  transfers issued at PRODUCE time through the ``BoundaryTransport`` seam
  (the one class a real-interconnect collective-permute implementation
  replaces) and a bounded per-stage activation stash (the 1F1B memory
  property: stage ``s`` stashes at most ``pp - s`` inputs);
* each (stage, microbatch) op emits a rank-LOCAL trace — stage-local layer
  names, microbatch-sized leaves — merged into the reference-shaped trace
  by the build-once ``core.merger.MergePlan`` (one jitted pack per stage:
  microbatch-axis concat + fused grad accumulation; names canonicalized
  via the same ``stage_layer_table`` the staged candidate uses) BEFORE any
  checking, numerically identical to ``merge_microbatch_traces``;
* the plan's packed per-stage gradients double as the source of the
  reference-named global tree for the (once-jitted) optimizer step.

Backward ops recompute their stage's forward from the stashed boundary
input inside ``jax.vjp`` (stage-granular activation checkpointing) — which
is exactly the surface the two schedule-layer bugs corrupt:

* ``pp_microbatch_order`` — the backward recompute reads the NEXT
  microbatch's stashed input, so gradients are accumulated against the
  wrong microbatch's activations.  Forward — and therefore the loss curve —
  is byte-identical to the correct schedule;
* ``pp_stale_boundary`` — stage ``i+1`` consumes the previous microbatch's
  boundary activation (a stale recv buffer).  Microbatch 0 is correct and
  every consumed tensor is a real activation, so the loss stays plausible.

Every per-stage forward/backward is jitted exactly once at engine build
(rewrites ride along as a dict *argument*, so localization-mode calls reuse
the same compiled steps per rewrite-name signature) — the supervisor's
``CandidateStep`` once-compiled contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.collector import (Trace, _make_probes, flatten_named,
                                  unflatten_named)
from repro.core.merger import MergePlan, canonical_stage_name
from repro.core.tap import TraceContext
from repro.models.model import block_apply
from repro.parallel.pp import stage_division, stage_layer_table


# ---------------------------------------------------------------------------
# Schedule (pure — property-tested in tests/test_pp1f1b.py)
# ---------------------------------------------------------------------------

def stage_tables(n_layers: int, pp_size: int,
                 bugs=frozenset()) -> list[list[tuple[int, int]]]:
    """Per-stage ``[(executed_layer, canonical_index), ...]`` — the flat
    ``stage_layer_table`` grouped by owning stage, i.e. the renaming each
    RANK would apply to its local trace (paper Fig 5)."""
    stages = stage_division(n_layers, pp_size, bugs)
    flat = stage_layer_table(n_layers, pp_size, bugs)
    out, i = [], 0
    for start, end in stages:
        out.append(flat[i:i + (end - start)])
        i += end - start
    return out


def stage_op_stream(pp_size: int, stage: int,
                    n_microbatches: int) -> list[tuple[str, int, int]]:
    """Canonical per-stage 1F1B op stream ``[("F"|"B", stage, mb), ...]``:
    ``min(M, pp - 1 - stage)`` warmup forwards, then one-forward-one-backward
    pairs, then cooldown backwards (Megatron's non-interleaved schedule)."""
    M = n_microbatches
    warm = min(M, pp_size - 1 - stage)
    ops = [("F", stage, m) for m in range(warm)]
    for i in range(M - warm):
        ops.append(("F", stage, warm + i))
        ops.append(("B", stage, i))
    ops += [("B", stage, m) for m in range(M - warm, M)]
    return ops


def walk_1f1b(streams, visit, max_per_visit: int | None = None) -> None:
    """Dependency-driven walk of per-stage 1F1B op streams: ``visit(d, s,
    m)`` fires as soon as the op's cross-stage dependency is met (forward
    (s, m) needs forward (s-1, m); backward (s, m) needs backward
    (s+1, m)), per-stage order fixed by the streams.  This is THE driver —
    the engine dispatches through it greedily (each stage runs as far
    ahead as its data allows) and ``schedule_1f1b`` replays it with
    ``max_per_visit=1`` (the clock-tick linearization), so the two can
    never drift."""
    S = len(streams)
    ptr = [0] * S
    done_f: set = set()
    done_b: set = set()
    remaining = sum(len(st) for st in streams)
    while remaining:
        progressed = False
        for s in range(S):
            taken = 0
            while ptr[s] < len(streams[s]) and (max_per_visit is None
                                                or taken < max_per_visit):
                d, _, m = streams[s][ptr[s]]
                ready = (d == "F" and (s == 0 or (s - 1, m) in done_f)) or \
                        (d == "B" and (s == S - 1 or (s + 1, m) in done_b))
                if not ready:
                    break
                visit(d, s, m)
                (done_f if d == "F" else done_b).add((s, m))
                ptr[s] += 1
                taken += 1
                remaining -= 1
                progressed = True
        if not progressed:       # impossible for a well-formed 1F1B stream
            raise RuntimeError("1F1B schedule deadlocked")


def schedule_1f1b(pp_size: int,
                  n_microbatches: int) -> list[tuple[str, int, int]]:
    """Global execution order: the clock-tick linearization of
    ``walk_1f1b`` (each stage advances at most one op per tick) — the host
    serialization of what per-rank processes execute concurrently."""
    streams = [stage_op_stream(pp_size, s, n_microbatches)
               for s in range(pp_size)]
    order: list[tuple[str, int, int]] = []
    walk_1f1b(streams, lambda d, s, m: order.append((d, s, m)),
              max_per_visit=1)
    return order


# ---------------------------------------------------------------------------
# Stage-boundary transport (the one-module seam for real interconnects)
# ---------------------------------------------------------------------------

class BoundaryTransport:
    """Stage-boundary activation/gradient communication for one iteration.

    The seam the engine sends/receives through — and the ONE module a real
    interconnect implementation (ICI collective-permute on a ``(pp,)`` mesh)
    would replace.  This host-device implementation issues the transfer at
    **send time** (``jax.device_put`` is async), so the copy to stage ``i+1``
    overlaps stage ``i``'s remaining compute instead of being issued only
    when the consumer is about to run.

    Buffers model per-link recv slots: ``recv`` does not consume (a stale
    consumer may re-read an old slot — the ``pp_stale_boundary`` surface);
    ``evict`` frees a slot once the schedule proves it dead, bounding live
    boundary buffers at two per stage pair.

    ``deadline_s`` (optional) bounds each recv: the consumer polls the
    transfer future and a producer that died or hung turns into a
    ``repro.supervise.watchdog.BoundaryTimeout`` — a loud, localized
    failure naming the stage link — instead of an infinite stall inside
    the schedule.  ``None`` (default) keeps the native blocking behavior.
    """

    def __init__(self, places, deadline_s=None):
        self.places = places
        self.deadline_s = deadline_s
        self._act: dict = {}        # (producer stage, mb) -> act on stage+1
        self._grad: dict = {}       # (consumer stage, mb) -> grad on stage

    def _await(self, value, what: str):
        if self.deadline_s is None:
            return value
        from repro.supervise.watchdog import wait_ready
        for leaf in jax.tree_util.tree_leaves(value):
            wait_ready(leaf, self.deadline_s, what)
        return value

    def send_act(self, stage: int, mb: int, value) -> None:
        """Stage ``stage``'s forward output for ``mb`` -> stage ``stage+1``
        (transfer issued NOW, ahead of consumption)."""
        self._act[(stage, mb)] = jax.device_put(value,
                                                self.places[stage + 1])

    def recv_act(self, stage: int, mb: int):
        """The boundary activation stage ``stage`` produced for ``mb``, as
        resident on stage ``stage+1`` (non-consuming read)."""
        return self._await(self._act[(stage, mb)],
                           f"boundary act {stage}->{stage + 1} mb{mb}")

    def evict_act(self, stage: int, mb: int) -> None:
        self._act.pop((stage, mb), None)

    def send_grad(self, stage: int, mb: int, value) -> None:
        """The cotangent for stage ``stage``'s output of ``mb`` (produced by
        stage ``stage+1``'s backward) -> stage ``stage``."""
        self._grad[(stage, mb)] = jax.device_put(value, self.places[stage])

    def recv_grad(self, stage: int, mb: int):
        return self._await(self._grad.pop((stage, mb)),
                           f"boundary grad {stage + 1}->{stage} mb{mb}")


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class PP1F1BEngine:
    """Multi-device 1F1B executor for the dense-arch candidate.

    One instance = one compiled pipeline: ``collect(params, batch)`` runs a
    full 1F1B training iteration (forward + backward + grad accumulation,
    NO optimizer step) and returns the merged reference-shaped trace, the
    reference-named global gradient tree (placed on the controller device)
    and the per-rank ``MergeReport``.
    """

    def __init__(self, model, ref_params, batch, pp_size: int,
                 n_microbatches: int, bugs=frozenset(),
                 dispatch: str = "concurrent",
                 boundary_deadline_s: float | None = None):
        cfg = model.cfg
        if cfg.arch_type != "dense":
            # homogeneous attn_mlp stacks only: stages with aux-producing
            # blocks (MoE) would need the per-stage aux losses communicated
            # to the loss stage, which this engine does not implement
            raise ValueError("the 1F1B engine covers dense arches only "
                             f"(got arch_type={cfg.arch_type!r})")
        if pp_size < 2:
            raise ValueError("the 1F1B pipeline needs pp >= 2 stages")
        if n_microbatches < 1:
            raise ValueError("need at least one microbatch")
        if not isinstance(ref_params.get("layers"), (list, tuple)):
            raise ValueError("1F1B partitions unstacked layer lists — "
                             "rebuild the model with scan_layers=False")
        B = int(np.shape(batch["tokens"])[0])
        if B % n_microbatches:
            raise ValueError(f"batch size {B} not divisible into "
                             f"{n_microbatches} microbatches")
        devs = jax.devices()
        if len(devs) < pp_size:
            raise RuntimeError(
                f"{pp_size} pipeline stages need {pp_size} devices; found "
                f"{len(devs)} {devs[0].platform} device(s)")
        self.model, self.cfg = model, cfg
        self.bugs = frozenset(bugs)
        self.pp, self.M = pp_size, n_microbatches
        self.mb_size = B // n_microbatches
        self.tied = cfg.tie_embeddings
        if dispatch not in ("concurrent", "ordered"):
            raise ValueError(f"unknown dispatch mode {dispatch!r}")
        self.dispatch = dispatch
        # optional per-recv deadline on stage-boundary transfers: a dead
        # producer becomes a loud BoundaryTimeout, not an infinite stall
        self.boundary_deadline_s = boundary_deadline_s
        self.stages = stage_division(cfg.n_layers, pp_size, self.bugs)
        self.tables = stage_tables(cfg.n_layers, pp_size, self.bugs)
        self.streams = [stage_op_stream(pp_size, s, n_microbatches)
                        for s in range(pp_size)]
        self.schedule = schedule_1f1b(pp_size, n_microbatches)
        self._plan: MergePlan | None = None
        self.meshes = [Mesh(np.array(devs[s:s + 1]), ("stage",))
                       for s in range(pp_size)]
        self.places = [NamedSharding(m, P()) for m in self.meshes]
        self.home = devs[0]     # controller: merged trace + optimizer step

        # tap discovery (chained per-stage eval_shape) + once-jitted steps
        sds = lambda v: jax.ShapeDtypeStruct(tuple(np.shape(v)),  # noqa: E731
                                             jnp.result_type(v))
        mb_sds = {k: jax.ShapeDtypeStruct(
            (self.mb_size,) + tuple(np.shape(v))[1:], jnp.result_type(v))
            for k, v in batch.items()}
        self._fwd, self._bwd = [], []
        self._probes, self._orders = [], []
        h_sds = None
        for s in range(pp_size):
            p_sds = jax.tree.map(sds, self._slice_params(ref_params, s))
            out_sds, taps_sds, order = self._discover(s, p_sds, h_sds,
                                                      mb_sds)
            self._probes.append({k: jax.device_put(v, self.places[s])
                                 for k, v in _make_probes(taps_sds, None,
                                                          True).items()})
            self._orders.append(order)
            self._fwd.append(jax.jit(self._fwd_fn(s)))
            self._bwd.append(jax.jit(self._bwd_fn(s)))
            if s < pp_size - 1:
                h_sds = out_sds

    # ---- partitioning ------------------------------------------------------
    def _slice_params(self, params, s: int) -> dict:
        """Stage ``s``'s rank-local parameter tree (stage-LOCAL layer list;
        embedding replicated on first/last stage when tied)."""
        start, end = self.stages[s]
        p = {"layers": [params["layers"][i] for i in range(start, end)]}
        if s == 0:
            p["embedding"] = params["embedding"]
        if s == self.pp - 1:
            p["final_norm"] = params["final_norm"]
            if self.tied:
                p["embedding"] = params["embedding"]
            else:
                p["lm_head"] = params["lm_head"]
        return p

    # ---- stage computation -------------------------------------------------
    def _apply(self, s: int, p, h, mb, ctx):
        """Stage forward with stage-LOCAL tap names: embeds on stage 0,
        applies the local layer slice, finishes with norm + loss on the
        last stage (loss = per-microbatch mean CE, so the mean over equal
        microbatches equals the reference full-batch loss)."""
        from repro.models.layers import _logits, cross_entropy, rmsnorm
        cfg = self.cfg
        if s == 0:
            h = self.model.embed(p, mb, ctx)
        # dense attn_mlp blocks have zero aux loss (enforced in __init__),
        # so only the loss stage needs to carry it
        aux = jnp.zeros((), jnp.float32)
        for local in range(len(self.tables[s])):
            with ctx.scope(f"layers.{local}"):
                h, a, _ = block_apply(p["layers"][local], cfg, "attn_mlp",
                                      h, ctx)
            if s == self.pp - 1:
                aux = aux + a
        if s < self.pp - 1:
            return h
        h = rmsnorm(p["final_norm"], h)
        h = ctx.tap("final_norm_out", h)
        e = (p["embedding"]["word_embeddings"] if self.tied
             else p["lm_head"])
        return cross_entropy(_logits(h, e), mb["labels"]) + aux

    def _discover(self, s, p_sds, h_sds, mb_sds):
        order: list[str] = []

        def f(p, h, mb):
            ctx = TraceContext("collect")
            out = self._apply(s, p, h, mb, ctx)
            order.clear()
            order.extend(ctx.fwd.keys())
            return out, ctx.fwd

        out_sds, taps_sds = jax.eval_shape(f, p_sds, h_sds, mb_sds)
        return out_sds, taps_sds, list(order)

    def _fwd_fn(self, s: int):
        def fwd(p, h, mb, rew):
            ctx = TraceContext("rewrite" if rew else "collect", rewrites=rew)
            out = self._apply(s, p, h, mb, ctx)
            return out, ctx.fwd
        return fwd

    def _bwd_fn(self, s: int):
        """Backward op: recompute the stage forward from the stashed input
        inside ``jax.vjp`` (with the act-grad zero probes as primals), seed
        with the downstream cotangent, return (input grad, param grads,
        act grads)."""
        def bwd(p, h, mb, g, rew, pr):
            if s == 0:
                def fn(pp_, prr):
                    ctx = TraceContext("rewrite" if rew else "collect",
                                       probes=prr, rewrites=rew)
                    return self._apply(s, pp_, None, mb, ctx)
                _, vjp = jax.vjp(fn, p, pr)
                dp, dpr = vjp(g)
                return None, dp, dpr

            def fn(pp_, hh, prr):
                ctx = TraceContext("rewrite" if rew else "collect",
                                   probes=prr, rewrites=rew)
                return self._apply(s, pp_, hh, mb, ctx)
            _, vjp = jax.vjp(fn, p, h, pr)
            dp, dh, dpr = vjp(g)
            return dh, dp, dpr
        return bwd

    # ---- batch / rewrite plumbing ------------------------------------------
    def _split_batch(self, batch) -> list[dict]:
        bs = self.mb_size
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        return [{k: v[m * bs:(m + 1) * bs] for k, v in b.items()}
                for m in range(self.M)]

    def _stage_rewrites(self, rewrites):
        """Canonical full-batch rewrites -> ``[stage][mb] -> {local: value}``
        (the inverse of the merger's renaming, sliced per microbatch)."""
        if not rewrites:
            return None
        bs = self.mb_size
        out = []
        for s in range(self.pp):
            per_mb = [dict() for _ in range(self.M)]
            for ln in self._orders[s]:
                cn = canonical_stage_name(ln, self.tables[s])
                if cn in rewrites:
                    v = jnp.asarray(rewrites[cn])
                    for m in range(self.M):
                        per_mb[m][ln] = jax.device_put(
                            v[m * bs:(m + 1) * bs], self.places[s])
            out.append(per_mb)
        return out

    # ---- the 1F1B iteration ------------------------------------------------
    def collect(self, params, batch, rewrites=None):
        """One full 1F1B training iteration.  Returns ``(merged_trace,
        grads_tree, merge_report)``; ``grads_tree`` is reference-named and
        placed on the controller device for the optimizer step.

        Per-stage ops are dispatched dependency-driven (each stage's next
        op launches as soon as its cross-stage input's device future
        exists), boundary transfers are issued at produce time through the
        ``BoundaryTransport`` seam, and the per-rank records are merged by
        the build-once ``MergePlan`` — all of it async dispatch; the host
        never blocks inside the iteration.
        """
        M, S = self.M, self.pp
        mbs = self._split_batch(batch)
        mb_first = [jax.device_put(mb, self.places[0]) for mb in mbs]
        mb_last = [jax.device_put(mb, self.places[-1]) for mb in mbs]
        rew = self._stage_rewrites(rewrites)
        ps = [jax.device_put(self._slice_params(params, s), self.places[s])
              for s in range(S)]
        cot = jax.device_put(jnp.float32(1.0 / M), self.places[-1])
        stale = "pp_stale_boundary" in self.bugs
        misorder = "pp_microbatch_order" in self.bugs

        tp = BoundaryTransport(self.places,
                               deadline_s=self.boundary_deadline_s)
        stash: list[dict] = [dict() for _ in range(S)]
        losses: list = [None] * M
        records: dict = {}             # (s, m, d) -> rank-local Trace

        def mb_arg(s, m):
            if s == 0:
                return mb_first[m]
            if s == S - 1:
                return mb_last[m]
            return None

        def run_op(d, s, m):
            r = rew[s][m] if rew else {}
            if d == "F":
                if s == 0:
                    h_in = None
                else:
                    # boundary recv: the stale-boundary bug re-reads the
                    # previous microbatch's recv slot
                    src = m - 1 if (stale and m > 0) else m
                    h_in = tp.recv_act(s - 1, src)
                out, taps = self._fwd[s](ps[s], h_in, mb_arg(s, m), r)
                stash[s][m] = h_in
                if s == S - 1:
                    losses[m] = out
                else:
                    # transfer to stage s+1 issued NOW — it overlaps this
                    # stage's (and every other stage's) in-flight compute
                    tp.send_act(s, m, out)
                if s > 0 and m > 0:
                    # recv-slot eviction: slot (s-1, k) feeds forward (s, k)
                    # and — under the stale-boundary bug — forward (s, k+1);
                    # once (s, m) ran, (s-1, m-1) is dead, so at most two
                    # slots live per stage pair
                    tp.evict_act(s - 1, m - 1)
                tr = Trace()
                tr.activations = dict(taps)
                tr.meta.update(stage=s, microbatch=m,
                               fwd_order=list(self._orders[s]))
            else:
                # the microbatch-order bug misindexes the activation stash
                # (and, on stage 0, the token microbatch it re-embeds)
                src = m + 1 if (misorder and (m + 1) in stash[s]) else m
                h_in = stash[s][src]
                mb_in = mb_arg(s, src if s == 0 else m)
                g = cot if s == S - 1 else tp.recv_grad(s, m)
                dh, dp, dpr = self._bwd[s](ps[s], h_in, mb_in, g, r,
                                           self._probes[s])
                del stash[s][m]
                if s > 0:
                    tp.send_grad(s - 1, m, dh)
                tr = Trace()
                tr.act_grads = dict(dpr)
                tr.param_grads = flatten_named(dp)
                tr.meta.update(stage=s, microbatch=m)
            records[(s, m, d)] = tr

        if self.dispatch == "ordered":
            for d, s, m in self.schedule:
                run_op(d, s, m)
        else:
            self._drive_concurrent(run_op)

        # canonical record order (driver-independent): the MergePlan
        # signature and the merged trace are identical either way
        rec_list = [(s, m, records[(s, m, d)])
                    for (s, m, d) in sorted(records,
                                            key=lambda k: (k[0], k[1], k[2]))]
        if self._plan is None:
            self._plan = MergePlan.build(rec_list, self.tables, M,
                                         place=self.home)
        merged, report = self._plan.execute(rec_list)
        stage_pg = self._plan.stage_param_grads
        if stage_pg is None:           # fell back (foreign record structure)
            stage_pg = {}
            for (s, m, d), tr in sorted(records.items()):
                if d != "B":
                    continue
                for n, g in tr.param_grads.raw_items():
                    g = jax.device_put(g, self.home)
                    key = (s, n)
                    stage_pg[key] = (stage_pg[key] + g if key in stage_pg
                                     else g)
        loss = losses[0]
        for m in range(1, M):
            loss = loss + losses[m]
        merged.loss = loss / M
        merged.meta["microbatches"] = M
        merged.meta["pp"] = S
        return merged, self._global_grads(params, stage_pg), report

    def _drive_concurrent(self, run_op):
        """Dependency-driven per-stage dispatch: launch each op the moment
        its cross-stage input's device future exists — no global
        clock-tick linearization, each stage runs as far ahead as its data
        allows.  Per-stage op order is exactly ``stage_op_stream``, so
        device execution (and with it every trace) is identical to the
        ordered drive."""
        walk_1f1b(self.streams, run_op)

    def _global_grads(self, params, stage_pg):
        """Per-stage accumulated grads ``{(stage, local name): leaf}`` (on
        the controller, courtesy of the merge plan's packed transfer) ->
        reference-named global tree.  Stage-local layer indices map to the
        EXECUTED global layers (a twice-executed layer's contributions sum,
        exactly like autodiff on the staged candidate); never-executed
        layers get zero grads; tied-embedding contributions from both
        pipeline ends are summed (the explicit tied-embedding reduction)."""
        named: dict = {}
        for (s, n), g in stage_pg.items():
            if n.startswith("layers."):
                start = self.stages[s][0]
                local, _, rest = n[len("layers."):].partition(".")
                tgt = f"layers.{start + int(local)}.{rest}"
            else:
                tgt = n
            named[tgt] = named[tgt] + g if tgt in named else g
        tpl = flatten_named(params)
        for n, v in tpl.items():
            if n not in named:
                named[n] = jnp.zeros(np.shape(v), jnp.result_type(v))
        return unflatten_named(named, params)


# ---------------------------------------------------------------------------
# Supervisor / harness entry points (the CandidateStep contract)
# ---------------------------------------------------------------------------

def make_pp1f1b_train_step(model, ref_params, opt, batch, pp_size: int,
                           microbatches: int, bugs=frozenset()):
    """Once-compiled stateful 1F1B candidate train step (supervisor
    contract): ``step(params, opt_state, batch) -> (Trace, new_params,
    new_opt_state)``.  The per-stage fwd/bwd jits and the optimizer update
    compile exactly once and are reused every supervised step and bisection
    replay."""
    eng = PP1F1BEngine(model, ref_params, batch, pp_size, microbatches,
                       bugs)
    upd = jax.jit(opt.update)

    def step(params, opt_state, b):
        tr, grads, _ = eng.collect(params, b)
        new_p, new_st, info = upd(params, grads, opt_state)
        tr.main_grads = flatten_named(info.main_grads)
        tr.params_post = flatten_named(new_p)
        tr.grad_norm = info.grad_norm
        return tr, new_p, new_st

    params0 = jax.tree.map(jnp.asarray, ref_params)
    return step, params0, opt.init(params0)


def make_pp1f1b_runner(model, params, pp_size: int, microbatches: int,
                       opt=None, opt_state=None, bugs=frozenset()):
    """``runner(batch, rewrites) -> Trace`` over the 1F1B engine — the
    rewrite-mode localization side of the candidate (engine built lazily
    from the first batch's shapes)."""
    eng = None

    def run(batch, rewrites=None) -> Trace:
        nonlocal eng
        if eng is None:
            eng = PP1F1BEngine(model, params, batch, pp_size, microbatches,
                               bugs)
        tr, grads, _ = eng.collect(params, batch, rewrites=rewrites)
        if opt is not None:
            st = opt_state if opt_state is not None else opt.init(params)
            new_p, _, info = opt.update(params, grads, st)
            tr.main_grads = flatten_named(info.main_grads)
            tr.params_post = flatten_named(new_p)
            tr.grad_norm = info.grad_norm
        return tr

    return run
