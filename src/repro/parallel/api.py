"""Candidate-runner builder: shard_map plumbing for the distributed GPT.

``make_candidate_runner`` turns (ArchConfig, ParallelConfig, reference
params) into a ``runner(batch, rewrites) -> Trace`` with the SAME canonical
tap names as the single-device reference — the distributed half of TTrace's
differential test.

Plumbing responsibilities:
  * build the ("dp","cp","tp") mesh and shard params/batch/probes per the
    generated annotations (the programmatic equivalent of the paper's Fig 2
    user annotations);
  * zigzag-permute sequence-dim inputs for context parallelism and
    un-permute collected taps back to logical order (paper Fig 6 layout);
  * two-phase tap discovery (shard_map needs out_specs before tracing);
  * post-backward gradient reductions over dp/cp/tp per tensor — the
    bug-injection site for the loss-scaling and missing-all-reduce bugs;
  * the optimizer step (plain AdamW or ZeRO-1) with main-grad and post-step
    parameter tracing.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.annotations import Annotations, ShardSpec
from repro.core.collector import (Trace, flatten_named, in_full_precision,
                                  unflatten_named)
from repro.core.tap import TraceContext
from repro.parallel.gpt import parallel_gpt_loss
from repro.parallel.layers import permute_from_zigzag, permute_to_zigzag
from repro.parallel.zero import zero1_update

MESH_AXES = {"dp": "dp", "cp": "cp", "tp": "tp", "sp": "tp"}


@dataclass(frozen=True)
class ParallelConfig:
    dp: int = 1
    cp: int = 1
    tp: int = 1
    sp: bool = False
    zero1: bool = False
    pp: int = 1                  # pipeline candidate (parallel.pp / pp1f1b)
    pp_schedule: str = "staged"  # staged (single-controller) | 1f1b
    microbatches: int = 1        # 1F1B microbatch count
    fp8: Optional[str] = None    # FP8 recipe: global | per_tensor | tile128
    bugs: frozenset = frozenset()

    @property
    def n_devices(self):
        # staged pp and fp8 are single-controller candidate recipes — they
        # model semantics (stage division, quantization), not placement;
        # the 1F1B engine places one pipeline stage per device
        base = self.dp * self.cp * self.tp
        if self.pp > 1 and self.pp_schedule == "1f1b":
            return base * self.pp
        return base

    @property
    def features(self) -> set:
        f = set()
        if self.dp > 1: f.add("dp")
        if self.cp > 1: f.add("cp")
        if self.tp > 1: f.add("tp")
        if self.sp: f.add("sp")
        if self.zero1: f.add("zero1")
        if self.pp > 1: f.add("pp")
        if self.pp > 1 and self.pp_schedule == "1f1b": f.add("1f1b")
        if self.fp8: f.add("fp8")
        return f

    @property
    def recipe_kind(self) -> str:
        """Which candidate implementation drives this config."""
        if self.fp8 and self.pp > 1:
            raise ValueError("pp + fp8 in one candidate is not supported")
        if self.pp_schedule not in ("staged", "1f1b"):
            raise ValueError(f"unknown pp_schedule {self.pp_schedule!r}")
        if self.fp8:
            return "fp8"
        if self.pp > 1:
            return "pp_1f1b" if self.pp_schedule == "1f1b" else "pp"
        return "shard_map"


def reference_device(pcfg: ParallelConfig):
    """The device the supervisor's reference step runs on; None with one
    device.

    Candidate recipes place from device 0 up (the shard_map mesh, the 1F1B
    per-stage submeshes, and device 0 for the single-controller recipes and
    the 1F1B controller's merged trace and optimizer step), so the last
    device is the one they load least.  When it lies outside the
    candidate's ``pcfg.n_devices`` the two steps run concurrently; when the
    candidate spans every device it still keeps the reference's state and
    step off the controller."""
    devs = jax.devices()
    return devs[-1] if len(devs) > 1 else None


def make_device_mesh(pcfg: ParallelConfig) -> Mesh:
    # the shard_map mesh covers the dp/cp/tp axes only — the 1F1B engine's
    # per-stage devices (the pp factor of n_devices) never join this mesh
    n = pcfg.dp * pcfg.cp * pcfg.tp
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"the dp={pcfg.dp} x cp={pcfg.cp} x tp={pcfg.tp} mesh needs {n} "
            f"devices; found {len(devs)} {devs[0].platform} device(s)")
    arr = np.array(devs[:n]).reshape(pcfg.dp, pcfg.cp, pcfg.tp)
    return Mesh(arr, ("dp", "cp", "tp"))


# ---------------------------------------------------------------------------
# Annotation generation (what a user would write by hand, paper Fig 2)
# ---------------------------------------------------------------------------

def build_annotations(cfg: ArchConfig, pcfg: ParallelConfig) -> Annotations:
    sp = pcfg.sp
    cp = pcfg.cp > 1
    seqspec = dict(cp_dim=1 if cp else None, cp_mode="zigzag",
                   sp_dim=1 if sp else None, dp_dim=0)
    params = {
        "embedding.word_embeddings": {"tp_dim": 0},
        "lm_head": {"tp_dim": 0},
        "layers.*.self_attention.linear_qkv.w": {"tp_dim": 1},
        "layers.*.self_attention.linear_qkv.b": {"tp_dim": 0},
        "layers.*.self_attention.linear_proj.w": {"tp_dim": 0},
        "layers.*.mlp.gate.w": {"tp_dim": 1},
        "layers.*.mlp.up.w": {"tp_dim": 1},
        "layers.*.mlp.down.w": {"tp_dim": 0},
        "layers.*.mlp.experts.gate": {"tp_dim": 0},   # expert dim
        "layers.*.mlp.experts.up": {"tp_dim": 0},
        "layers.*.mlp.experts.down": {"tp_dim": 0},
    }
    acts = {
        "embedding/output": seqspec,
        "layers.*.self_attention/input": seqspec,
        "layers.*.self_attention/core_attn_out":
            {"tp_dim": -1, "cp_dim": 1 if cp else None, "cp_mode": "zigzag",
             "dp_dim": 0},
        "layers.*.self_attention/output": seqspec,
        "layers.*.mlp/input": seqspec,
        "layers.*.mlp/output": seqspec,
        "layers.*.mlp/router_logits":
            {"cp_dim": 1 if cp else None, "cp_mode": "zigzag", "dp_dim": 0},
        "final_norm_out": seqspec,
    }
    return Annotations.from_dict({"params": params, "acts": acts})


def spec_to_pspec(spec: ShardSpec, ndim: int, pcfg: ParallelConfig) -> P:
    """ShardSpec -> PartitionSpec on the ("dp","cp","tp") mesh."""
    dims: dict[int, list[str]] = {}

    def add(axis, mesh_axis, active):
        d = spec.dim_for(axis)
        if d is None or not active:
            return
        dims.setdefault(d % ndim, []).append(mesh_axis)

    # outer-to-inner order must match annotations.AXES: dp, ep, cp, tp, sp
    add("dp", "dp", pcfg.dp > 1)
    add("ep", "tp", pcfg.tp > 1)
    add("cp", "cp", pcfg.cp > 1)
    add("tp", "tp", pcfg.tp > 1)
    add("sp", "tp", pcfg.sp)
    entries = []
    for i in range(ndim):
        ax = dims.get(i, [])
        entries.append(None if not ax else (ax[0] if len(ax) == 1
                                            else tuple(ax)))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def sizes_coords(pcfg: ParallelConfig):
    return {"dp": pcfg.dp, "cp": pcfg.cp, "tp": pcfg.tp,
            "sp": pcfg.tp if pcfg.sp else 1}


# ---------------------------------------------------------------------------
# Gradient reduction rules (the bug surface)
# ---------------------------------------------------------------------------

def _needs_tp_reduce(name: str, pcfg: ParallelConfig) -> bool:
    if name.endswith("q_norm") or name.endswith("k_norm"):
        return pcfg.tp > 1          # head-sharded compute, always partial
    if name.endswith("router"):
        # expert-parallel: each rank backprops only its local experts'
        # combine weights into the (replicated) router — the grads are
        # partial and must be all-reduced over the EP (= tp) group.  This is
        # the sync Megatron's bug 6 family is about.
        return pcfg.tp > 1
    norm_like = name.endswith(("input_norm", "post_attn_norm", "final_norm"))
    return pcfg.sp and pcfg.tp > 1 and norm_like


def reduce_param_grads(pg_named: dict, pcfg: ParallelConfig, bugs):
    out = {}
    for name, g in pg_named.items():
        if pcfg.dp > 1:
            g = jax.lax.psum(g, "dp")
            if "dp_wrong_loss_scale" not in bugs:
                g = g / pcfg.dp
        if pcfg.cp > 1:
            skip_cp = ("tp_cp_wrong_norm_grad" in bugs
                       and name.endswith("input_norm") and pcfg.tp > 1)
            if skip_cp:
                from repro.parallel.layers import one_rank
                g = one_rank(g, "cp")   # per-rank partial, silently wrong
            else:
                g = jax.lax.psum(g, "cp")
                if "cp_wrong_loss_scale" not in bugs:
                    g = g / pcfg.cp
        if _needs_tp_reduce(name, pcfg):
            skip = (("sp_layernorm_not_synced" in bugs
                     and name.endswith("post_attn_norm"))
                    or ("tp_missing_grad_allreduce" in bugs
                        and name.endswith("input_norm")))
            if skip:
                from repro.parallel.layers import one_rank
                g = one_rank(g, "tp")   # per-rank partial, silently wrong
            else:
                g = jax.lax.psum(g, "tp")
        out[name] = g
    return out


def reduce_act_grads(ag: dict, ann: Annotations, pcfg: ParallelConfig, bugs):
    """Activation-gradient (probe) scaling.  The tp accumulation is already
    handled by the f/g conjugate operators inside the layers; what remains is
    the dp/cp loss averaging — the same scale factors whose bugs (3, 4) the
    paper catalogues."""
    out = {}
    for name, g in ag.items():
        if pcfg.tp > 1 and name.endswith("router_logits"):
            # dispatch + (tp-partialized) aux contributions sum over tp
            g = jax.lax.psum(g, "tp")
        if pcfg.dp > 1 and "dp_wrong_loss_scale" not in bugs:
            g = g / pcfg.dp
        if pcfg.cp > 1 and "cp_wrong_loss_scale" not in bugs:
            g = g / pcfg.cp
        out[name] = g
    return out


# ---------------------------------------------------------------------------
# Compiled-step caches
# ---------------------------------------------------------------------------
#
# make_candidate_runner used to rebuild (and re-trace) a fresh shard_map per
# call; every TTrace check paid full tracing + compilation again.  Both the
# tap-discovery result and the jitted step are pure functions of
# (ArchConfig, ParallelConfig, input signature), so they are cached at module
# level keyed on exactly that — repeated runner builds (and the supervisor's
# bisection replays) reuse one compiled step per side.

_TAP_CACHE: dict = {}     # (cfg, pcfg, psig, bsig) -> (names, ti)
_STEP_CACHE: dict = {}    # + (probe names, rewrite names, jit) -> callable


def _abstract_sig(named: dict) -> tuple:
    return tuple((n, tuple(np.shape(v)), str(jnp.result_type(v)))
                 for n, v in sorted(named.items()))


def clear_step_cache():
    """Drop cached compiled candidate steps (tests / mesh reconfiguration)."""
    _TAP_CACHE.clear()
    _STEP_CACHE.clear()


# ---------------------------------------------------------------------------
# Recipe dispatch (pp / fp8 candidates share the supervisor contract)
# ---------------------------------------------------------------------------

def _check_recipe_pcfg(cfg: ArchConfig, pcfg: ParallelConfig) -> None:
    if pcfg.dp * pcfg.cp * pcfg.tp != 1 or pcfg.zero1 or pcfg.sp:
        raise ValueError(
            f"the {pcfg.recipe_kind} candidate cannot combine with "
            f"dp/cp/tp/zero1 (got {pcfg})")
    if pcfg.microbatches > 1 and pcfg.recipe_kind != "pp_1f1b":
        # only the 1F1B engine executes microbatches; anywhere else the
        # flag would be a silent no-op
        raise ValueError(
            f"microbatches={pcfg.microbatches} applies to the 1F1B "
            f"pipeline only (recipe {pcfg.recipe_kind})")
    if cfg.arch_type != "dense":
        # fp8 quantizes the dense MLP matmuls only (MoE expert matmuls are
        # a ROADMAP follow-up) and the pp losses partition homogeneous
        # attn_mlp stacks; running other arches would be a silent no-op —
        # the injected bug never expresses and a clean PASS means nothing
        raise ValueError(
            f"the {pcfg.recipe_kind} candidate covers dense arches only "
            f"(got arch_type={cfg.arch_type!r})")


def _recipe_runner(cfg: ArchConfig, pcfg: ParallelConfig, ref_params,
                   opt=None, opt_state=None):
    _check_recipe_pcfg(cfg, pcfg)
    from repro.models.model import Model
    model = Model(cfg)
    if pcfg.recipe_kind == "pp":
        from repro.parallel.pp import make_pp_runner
        return make_pp_runner(model, ref_params, pcfg.pp, opt=opt,
                              opt_state=opt_state, bugs=pcfg.bugs)
    if pcfg.recipe_kind == "pp_1f1b":
        from repro.parallel.pp1f1b import make_pp1f1b_runner
        return make_pp1f1b_runner(model, ref_params, pcfg.pp,
                                  pcfg.microbatches, opt=opt,
                                  opt_state=opt_state, bugs=pcfg.bugs)
    from repro.precision.fp8 import make_fp8_runner
    return make_fp8_runner(model, ref_params, pcfg.fp8, opt=opt,
                           opt_state=opt_state, bugs=pcfg.bugs)


def _recipe_train_step(cfg: ArchConfig, pcfg: ParallelConfig, ref_params,
                       opt, batch):
    _check_recipe_pcfg(cfg, pcfg)
    from repro.models.model import Model
    model = Model(cfg)
    if pcfg.recipe_kind == "pp":
        from repro.parallel.pp import make_pp_train_step
        return make_pp_train_step(model, ref_params, opt, batch, pcfg.pp,
                                  bugs=pcfg.bugs)
    if pcfg.recipe_kind == "pp_1f1b":
        from repro.parallel.pp1f1b import make_pp1f1b_train_step
        return make_pp1f1b_train_step(model, ref_params, opt, batch,
                                      pcfg.pp, pcfg.microbatches,
                                      bugs=pcfg.bugs)
    from repro.precision.fp8 import make_fp8_train_step
    return make_fp8_train_step(model, ref_params, opt, batch, pcfg.fp8,
                               bugs=pcfg.bugs)


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def qkv_permutation(cfg: ArchConfig, tp: int) -> np.ndarray:
    """Column permutation mapping the reference fused-QKV layout [Q|K|V] to
    the tensor-parallel layout [q_0|k_0|v_0 | q_1|k_1|v_1 | ...] so that a
    contiguous tp shard holds its own heads' q, k and v.

    This is the paper's "mapping of semantics" problem in miniature: the
    candidate framework stores the same logical parameter in a different
    physical layout, and the tensor canonical mapping must undo it."""
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = np.arange(H * D).reshape(tp, -1)
    k = H * D + np.arange(Hkv * D).reshape(tp, -1)
    v = (H + Hkv) * D + np.arange(Hkv * D).reshape(tp, -1)
    return np.concatenate([np.concatenate([q[r], k[r], v[r]])
                           for r in range(tp)])


def layout_maps(cfg: ArchConfig, tp: int):
    """``(to_candidate, from_candidate)`` leaf mappers over the QKV layout
    permutation — the single source of the reference<->candidate parameter
    layout for the one-shot runner AND the supervisor's train step."""
    perm = qkv_permutation(cfg, tp)
    inv_perm = np.argsort(perm)

    def to_candidate(name, leaf):
        if name.endswith("linear_qkv.w"):
            return leaf[:, perm]
        if name.endswith("linear_qkv.b"):
            return leaf[perm]
        return leaf

    def from_candidate(name, leaf):
        if name.endswith("linear_qkv.w"):
            return leaf[:, inv_perm]
        if name.endswith("linear_qkv.b"):
            return leaf[inv_perm]
        return leaf

    return to_candidate, from_candidate


class _Plumbing:
    """Everything derived from (cfg, pcfg, params structure) that both
    candidate step builders share: mesh, annotations, layout mappers,
    partition specs, the shard_map body, and the zigzag un-permute."""

    def __init__(self, cfg: ArchConfig, pcfg: ParallelConfig,
                 ref_params: dict):
        self.cfg, self.pcfg = cfg, pcfg
        self.mesh = make_device_mesh(pcfg)
        self.ann = build_annotations(cfg, pcfg)
        self.to_cand, self.from_cand = layout_maps(cfg, pcfg.tp)
        # the QKV permutation reorders columns but never changes shape, so
        # candidate-layout abstract shapes == reference shapes
        named = flatten_named(ref_params)
        self.param_shapes = {n: jax.ShapeDtypeStruct(tuple(l.shape),
                                                     jnp.result_type(l))
                             for n, l in named.items()}
        self.psig = _abstract_sig(self.param_shapes)
        self.param_pspecs = {
            n: spec_to_pspec(self.ann.param_spec(n), l.ndim, pcfg)
            for n, l in self.param_shapes.items()}
        self.param_specs_tree = unflatten_named(dict(self.param_pspecs),
                                                ref_params)
        self.params_sds = unflatten_named(dict(self.param_shapes),
                                          ref_params)
        bspec = P("dp" if pcfg.dp > 1 else None,
                  "cp" if pcfg.cp > 1 else None)
        self.batch_spec = {"tokens": bspec, "labels": bspec}
        self.loss_axes = tuple(a for a, n in (("dp", pcfg.dp),
                                              ("cp", pcfg.cp)) if n > 1)

    def body(self, p, bb, probes, rew):
        """shard_map body: traced forward + backward + grad reductions."""
        cfg, pcfg, bugs = self.cfg, self.pcfg, self.pcfg.bugs

        def local_loss(pp, pr):
            ctx = TraceContext("rewrite" if rew else "collect",
                               probes=pr, rewrites=rew or {})
            gloss, rloss = parallel_gpt_loss(pp, bb, cfg, pcfg.sp, bugs, ctx)
            return gloss, (ctx.fwd, rloss)
        (_, (taps, rloss)), (pgt, ag) = jax.value_and_grad(
            local_loss, argnums=(0, 1), has_aux=True)(p, probes)
        pg = flatten_named(pgt)
        pg = reduce_param_grads(pg, pcfg, bugs)
        ag = reduce_act_grads(ag, self.ann, pcfg, bugs)
        loss = rloss
        if self.loss_axes:
            loss = jax.lax.psum(loss, self.loss_axes) / (pcfg.dp * pcfg.cp)
        return loss, taps, unflatten_named(pg, pgt), ag

    def taps_for(self, batch_abstract: dict):
        """Cached tap discovery for one batch signature: returns
        ``(tap_key, names, ti, act_pspecs, probes, probe_specs)``.
        Discovery is a full abstract trace of the forward — cached at module
        level so repeated runner builds and supervisor replays skip it."""
        cfg, pcfg = self.cfg, self.pcfg
        b_sds = {k: jax.ShapeDtypeStruct(tuple(np.shape(v)),
                                         jnp.result_type(v))
                 for k, v in batch_abstract.items()}
        tap_key = (cfg, pcfg, self.psig, _abstract_sig(b_sds))
        cached = _TAP_CACHE.get(tap_key)
        if cached is None:
            bugs = pcfg.bugs
            ti = {}

            def body_d(p, bb):
                ctx = TraceContext("collect")
                parallel_gpt_loss(p, bb, cfg, pcfg.sp, bugs, ctx)[0]
                ti.clear()
                ti.update({k: (v.shape, v.dtype)
                           for k, v in ctx.fwd.items()})
                return jnp.zeros(())
            jax.eval_shape(shard_map(
                body_d, mesh=self.mesh,
                in_specs=(self.param_specs_tree, self.batch_spec),
                out_specs=P(), check_vma=False), self.params_sds, b_sds)
            cached = _TAP_CACHE[tap_key] = (list(ti), ti)
        names, ti = cached
        pspecs = {n: spec_to_pspec(self.ann.act_spec(n), len(ti[n][0]), pcfg)
                  for n in names}
        szs = sizes_coords(pcfg)

        def gshape(n):
            shape = list(ti[n][0])
            spec = self.ann.act_spec(n)
            for ax in ("dp", "cp", "tp", "sp"):
                d = spec.dim_for(ax)
                if d is not None and szs.get(ax, 1) > 1:
                    shape[d % len(shape)] *= szs[ax]
            return tuple(shape)

        probes = {n: jnp.zeros(gshape(n), jnp.float32) for n in names
                  if jnp.issubdtype(ti[n][1], jnp.floating)}
        probe_specs = {n: pspecs[n] for n in probes}
        return tap_key, names, ti, pspecs, probes, probe_specs

    def cached_shard_map(self, tap_key, pspecs, probe_specs, rew_specs,
                        probes, jit: bool):
        """The compiled (or raw) shard-mapped step for one signature.

        It takes the batch and rewrites and returns the taps in LOGICAL
        sequence order: the zigzag (un)permutation of context parallelism
        is part of the traced step."""
        step_key = tap_key + (tuple(probes), tuple(sorted(rew_specs)),
                              bool(jit))
        fn = _STEP_CACHE.get(step_key)
        if fn is None:
            sm = shard_map(
                self.body, mesh=self.mesh,
                in_specs=(self.param_specs_tree, self.batch_spec,
                          probe_specs, rew_specs),
                out_specs=(P(), pspecs, self.param_specs_tree,
                           {n: pspecs[n] for n in probes}),
                check_vma=False)

            def logical(p, b, pr, rew):
                b = {k: permute_to_zigzag(b[k], self.pcfg.cp, 1)
                     for k in ("tokens", "labels")}
                rew = {n: self.zig(n, v, permute_to_zigzag)
                       for n, v in rew.items()}
                loss, taps, pgt, ag = sm(p, b, pr, rew)
                taps = {n: self.zig(n, v, permute_from_zigzag)
                        for n, v in taps.items()}
                ag = {n: self.zig(n, v, permute_from_zigzag)
                      for n, v in ag.items()}
                return loss, taps, pgt, ag
            fn = _STEP_CACHE[step_key] = jax.jit(logical) if jit else logical
        return fn

    def zig(self, n, x, permute):
        """``permute`` tap ``n`` along its context-parallel dim (identity
        when cp == 1 or the tap has none)."""
        spec = self.ann.act_spec(n)
        if self.pcfg.cp > 1 and spec.cp_dim is not None:
            return permute(x, self.pcfg.cp, spec.cp_dim % x.ndim)
        return x


def make_candidate_runner(cfg: ArchConfig, pcfg: ParallelConfig,
                          ref_params: dict, opt=None, opt_state=None,
                          jit: bool = True):
    """Build ``runner(batch, rewrites) -> Trace`` for the candidate recipe:
    the shard_map distributed GPT, or (dispatching on ``pcfg``) the staged
    pipeline / FP8 candidates."""
    if pcfg.recipe_kind != "shard_map":
        return in_full_precision(
            _recipe_runner(cfg, pcfg, ref_params, opt, opt_state))
    pl = _Plumbing(cfg, pcfg, ref_params)
    bugs = pcfg.bugs

    # shard the (layout-mapped) reference params onto the mesh
    sharded = {}
    for name, leaf in flatten_named(ref_params).items():
        sh = NamedSharding(pl.mesh, pl.param_pspecs[name])
        sharded[name] = jax.device_put(pl.to_cand(name, leaf), sh)
    params = unflatten_named(sharded, ref_params)

    def prep_batch(batch):
        return {k: jax.device_put(jnp.asarray(batch[k]),
                                  NamedSharding(pl.mesh, pl.batch_spec[k]))
                for k in ("tokens", "labels")}

    def _run(batch, rewrites=None) -> Trace:
        b = prep_batch(batch)
        tap_key, names, ti, pspecs, probes, probe_specs = pl.taps_for(b)
        rew_in = {n: jax.device_put(jnp.asarray(v),
                                    NamedSharding(pl.mesh, pspecs[n]))
                  for n, v in (rewrites or {}).items() if n in names}
        rew_specs = {n: pspecs[n] for n in rew_in}

        fn = pl.cached_shard_map(tap_key, pspecs, probe_specs, rew_specs,
                                 probes, jit)
        loss, taps, pgt, ag = fn(params, b, probes, rew_in)

        tr = Trace()
        tr.loss = float(loss)
        # leaves stay device-resident jax.Arrays — the batched checker reads
        # them in place and only reduction scalars reach the host
        tr.activations = {n: taps[n] for n in names}
        tr.act_grads = {n: ag[n] for n in names if n in ag}
        pg_named = {k: pl.from_cand(k, v)
                    for k, v in flatten_named(pgt).items()}
        tr.param_grads = dict(pg_named)
        tr.meta["fwd_order"] = names
        tr.meta["annotations"] = pl.ann
        tr.meta["pcfg"] = pcfg

        if opt is not None:
            st = opt_state if opt_state is not None else opt.init(ref_params)
            grads_tree = unflatten_named(
                {k: jnp.asarray(v) for k, v in pg_named.items()}, ref_params)
            if pcfg.zero1:
                new_p, _, info = zero1_update(opt, ref_params, grads_tree,
                                              st, pcfg.dp, bugs)
            else:
                new_p, _, info = opt.update(ref_params, grads_tree, st)
            tr.main_grads = flatten_named(info.main_grads)
            tr.params_post = flatten_named(new_p)
            tr.grad_norm = float(info.grad_norm)
        return tr

    return in_full_precision(_run)


# ---------------------------------------------------------------------------
# Stateful candidate train step (the supervisor's lockstep contract)
# ---------------------------------------------------------------------------

def make_candidate_train_step(cfg: ArchConfig, pcfg: ParallelConfig,
                              ref_params: dict, opt, batch):
    """Once-compiled FULL candidate train step with trace collection.

    ``make_candidate_runner`` is stateless — it re-shards the reference
    params every call and applies the optimizer step eagerly on the host.
    The streaming supervisor instead threads the candidate's own
    (params, opt_state) through N steps, so the whole step — layout mapping,
    shard_map forward/backward, gradient reductions, the (possibly buggy
    ZeRO) optimizer update and the zigzag un-permutation of the taps — is
    fused into ONE jitted callable, compiled once against the template
    ``batch`` shapes.

    Persistent state lives in REFERENCE layout (fused-QKV order, host
    default placement); the step maps it to the candidate layout and mesh
    sharding internally.  Returns ``(step, params0, opt_state0)`` with
    ``step(params, opt_state, batch) -> (Trace, new_params, new_opt_state)``.
    Trace sections stay device-resident; loss/grad_norm stay device scalars.

    Dispatches on ``pcfg.recipe_kind``: the pipeline-parallel and FP8
    candidates return their own once-compiled steps under the same contract
    (``parallel.pp`` / ``precision.fp8``).
    """
    if pcfg.recipe_kind != "shard_map":
        step, params0, state0 = _recipe_train_step(cfg, pcfg, ref_params,
                                                   opt, batch)
        return in_full_precision(step), params0, state0
    pl = _Plumbing(cfg, pcfg, ref_params)
    bugs = pcfg.bugs
    tap_key, names, ti, pspecs, probes, probe_specs = pl.taps_for(
        {k: batch[k] for k in ("tokens", "labels")})
    # raw (unjitted) shard_map — jitted once below as part of the full step
    sm = pl.cached_shard_map(tap_key, pspecs, probe_specs, {}, probes,
                             jit=False)

    def cand_step(params, opt_state, b, pr):
        cand = unflatten_named(
            {n: pl.to_cand(n, l) for n, l in flatten_named(params).items()},
            params)
        loss, taps, pgt, ag = sm(cand, b, pr, {})
        pg_named = {k: pl.from_cand(k, v)
                    for k, v in flatten_named(pgt).items()}
        grads_tree = unflatten_named(pg_named, params)
        if pcfg.zero1:
            new_p, new_st, info = zero1_update(opt, params, grads_tree,
                                               opt_state, pcfg.dp, bugs)
        else:
            new_p, new_st, info = opt.update(params, grads_tree, opt_state)
        return (loss, taps, pg_named, ag, flatten_named(info.main_grads),
                info.grad_norm, new_p, new_st)

    step_c = jax.jit(cand_step)

    @in_full_precision
    def step(params, opt_state, batch) -> tuple[Trace, dict, dict]:
        bb = {k: batch[k] for k in ("tokens", "labels")}
        (loss, taps, pg_named, ag, main_grads, grad_norm,
         new_p, new_st) = step_c(params, opt_state, bb, probes)
        tr = Trace()
        tr.loss = loss
        tr.grad_norm = grad_norm
        tr.activations = {n: taps[n] for n in names}
        tr.act_grads = {n: ag[n] for n in names if n in ag}
        tr.param_grads = dict(pg_named)
        tr.main_grads = main_grads
        tr.params_post = flatten_named(new_p)
        tr.meta["fwd_order"] = list(names)
        tr.meta["annotations"] = pl.ann
        tr.meta["pcfg"] = pcfg
        return tr, new_p, new_st

    # commit the persistent state to the mesh (replicated): the step's
    # shard_map re-shards internally, jit accepts mesh-committed inputs, and
    # checkpoint restores (which inherit the template's sharding) come back
    # mesh-compatible for bisection replay
    rep = NamedSharding(pl.mesh, P())
    params0 = jax.device_put(jax.tree.map(jnp.asarray, ref_params), rep)
    state0 = jax.device_put(opt.init(params0), rep)
    return step, params0, state0


# ---------------------------------------------------------------------------
# Plain (trace-free) distributed training step — used by the loss-curve
# blindness demo (paper Fig 1) and the detection-latency benchmark (§6.4):
# the "naive debugging practice" trains the candidate and watches the loss.
# ---------------------------------------------------------------------------

def make_plain_train_step(cfg: ArchConfig, pcfg: ParallelConfig,
                          ref_params: dict, opt):
    """Returns (step_fn, params0, opt_state0): a jitted full train step of
    the distributed candidate (bugs included) without any tracing."""
    mesh = make_device_mesh(pcfg)
    ann = build_annotations(cfg, pcfg)
    bugs = pcfg.bugs
    perm = qkv_permutation(cfg, pcfg.tp)
    inv_perm = np.argsort(perm)

    def to_cand(name, leaf):
        if name.endswith("linear_qkv.w"):
            return leaf[:, perm]
        if name.endswith("linear_qkv.b"):
            return leaf[perm]
        return leaf

    named = {n: to_cand(n, l) for n, l in flatten_named(ref_params).items()}
    pspecs = {n: spec_to_pspec(ann.param_spec(n), l.ndim, pcfg)
              for n, l in named.items()}
    params = unflatten_named(
        {n: jax.device_put(l, NamedSharding(mesh, pspecs[n]))
         for n, l in named.items()}, ref_params)
    spec_tree = unflatten_named(pspecs, ref_params)
    bspec = P("dp" if pcfg.dp > 1 else None, "cp" if pcfg.cp > 1 else None)
    loss_axes = tuple(a for a, n in (("dp", pcfg.dp), ("cp", pcfg.cp))
                      if n > 1)

    def body(p, b):
        gloss, rloss = parallel_gpt_loss(p, b, cfg, pcfg.sp, bugs, None)
        grads = jax.grad(lambda pp: parallel_gpt_loss(
            pp, b, cfg, pcfg.sp, bugs, None)[0])(p)
        pg = reduce_param_grads(flatten_named(grads), pcfg, bugs)
        if loss_axes:
            rloss = jax.lax.psum(rloss, loss_axes) / (pcfg.dp * pcfg.cp)
        return rloss, unflatten_named(pg, grads)

    sm = shard_map(body, mesh=mesh,
                   in_specs=(spec_tree, {"tokens": bspec, "labels": bspec}),
                   out_specs=(P(), spec_tree), check_vma=False)

    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        batch = {k: permute_to_zigzag(v, pcfg.cp, 1)
                 for k, v in batch.items()}
        loss, grads = sm(params, batch)
        params, opt_state, info = opt.update(params, grads, opt_state)
        return params, opt_state, loss

    def prep(batch):
        return {k: jax.device_put(jnp.asarray(batch[k]),
                                  NamedSharding(mesh, bspec))
                for k in ("tokens", "labels")}

    return step, prep, params, opt_state
