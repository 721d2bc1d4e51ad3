"""Model assembly: builds every assigned architecture from an ArchConfig.

A model is a sequence of *segments*; each segment is either a stack of
identical layers (optionally executed with ``jax.lax.scan`` over stacked
parameters — the big dry-run configs) or a single block (e.g. zamba2's
shared-parameter attention block, deepseek's leading dense-FFN layer).

Public API (pure functions of params):
    m = Model(cfg)
    params = m.init(rng)
    h             = m.forward(params, batch, ctx)
    loss, metrics = m.loss(params, batch, ctx)
    cache         = m.init_cache(batch_size, seq_len)
    logits, cache = m.decode_step(params, cache, tokens, pos)

VLM / audio frontends are stubs per the assignment: ``batch`` carries
precomputed patch embeddings / frame features; the trained projector and the
transformer backbone are real.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.tap import ensure_ctx, TraceContext
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    chunked_cross_entropy, cross_entropy, embed_init, gelu_mlp, gelu_mlp_init,
    linear, linear_init, rmsnorm, swiglu_mlp, swiglu_mlp_init, _logits,
)
from repro.sharding.rules import constrain

# benchmarks/roofline sets this to force scan-free primitives (XLA counts
# loop bodies once, so cost analysis needs unrolled HLO)
COST_MODE = False


@dataclass(frozen=True)
class Segment:
    name: str          # params key; also the tap scope
    kind: str          # attn_mlp | attn_moe | rwkv | mamba | shared_attn
    n: int             # number of layers in this segment
    scan: bool         # lax.scan over stacked params
    layer0: int        # global index of the first layer (canonical naming)
    shared: bool = False  # params live under the shared key, not per-segment


def build_plan(cfg: ArchConfig) -> list[Segment]:
    segs: list[Segment] = []
    L = cfg.n_layers
    sc = cfg.scan_layers
    if cfg.arch_type in ("dense", "vlm", "audio"):
        if L > 0:
            segs.append(Segment("layers", "attn_mlp", L, sc and L > 1, 0))
    elif cfg.arch_type == "moe":
        nd = min(cfg.moe.n_dense_layers, L)
        if nd:
            segs.append(Segment("dense_layers", "attn_dense_mlp", nd,
                                False, 0))
        if L - nd > 0:
            segs.append(Segment("layers", "attn_moe", L - nd,
                                sc and L - nd > 1, nd))
    elif cfg.arch_type == "ssm":
        segs.append(Segment("layers", "rwkv", L, sc and L > 1, 0))
    elif cfg.arch_type == "hybrid":
        k = cfg.hybrid.attn_every
        i = 0
        g = 0
        while i < L:
            n = min(k, L - i)
            segs.append(Segment(f"mamba{g}", "mamba", n, sc and n > 1, i))
            i += n
            if i <= L - 0 and n == k and cfg.hybrid.shared_attn:
                segs.append(Segment(f"shared_attn_{g}", "shared_attn", 1,
                                    False, i, shared=True))
            g += 1
    else:
        raise ValueError(cfg.arch_type)
    return segs


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _out_scale(cfg):  # megatron-style scaled residual-output init
    import math
    return 0.02 / math.sqrt(2.0 * max(cfg.n_layers, 1))


def block_init(rng, cfg: ArchConfig, kind: str, dtype):
    osc = float(_out_scale(cfg))
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    if kind in ("attn_mlp", "attn_dense_mlp", "attn_moe", "shared_attn"):
        p = {"input_norm": jnp.ones((cfg.d_model,), dtype),
             "post_attn_norm": jnp.ones((cfg.d_model,), dtype)}
        if cfg.attn == "mla":
            p["self_attention"] = attn_mod.mla_init(k1, cfg, dtype, osc)
        else:
            p["self_attention"] = attn_mod.gqa_init(k1, cfg, dtype, osc)
        if kind == "attn_moe":
            p["mlp"] = moe_mod.moe_init(k2, cfg, dtype, osc)
        elif kind == "attn_dense_mlp":
            dff = cfg.moe.d_ff_dense or cfg.d_ff
            p["mlp"] = swiglu_mlp_init(k2, cfg.d_model, dff, dtype, osc)
        elif cfg.arch_type == "audio":
            p["mlp"] = gelu_mlp_init(k2, cfg.d_model, cfg.d_ff, dtype, osc)
        else:
            p["mlp"] = swiglu_mlp_init(k2, cfg.d_model, cfg.d_ff, dtype, osc)
        return p
    if kind == "rwkv":
        p = ssm_mod.rwkv6_init(k1, cfg, dtype, osc)
        p["input_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["post_tm_norm"] = jnp.ones((cfg.d_model,), dtype)
        return p
    if kind == "mamba":
        return {"input_norm": jnp.ones((cfg.d_model,), dtype),
                "mixer": ssm_mod.mamba2_init(k1, cfg, dtype, osc)}
    raise ValueError(kind)


def block_apply(p, cfg: ArchConfig, kind: str, x, ctx, cache=None, pos=None,
                decode=False, use_kernel=False, precision=None):
    """Returns (x, aux_loss, new_cache).  ``precision`` (an optional
    ``repro.precision.fp8.Precision``) routes the MLP matmuls through the
    FP8 recipe; everything else stays in the compute dtype."""
    ctx = ensure_ctx(ctx)
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn_mlp", "attn_dense_mlp", "attn_moe", "shared_attn"):
        # named scopes (attn, mlp, norm) only label the compiled program's
        # operations, so a profile says which block each one belongs to
        with jax.named_scope("norm"):
            h = rmsnorm(p["input_norm"], x)
        with ctx.scope("self_attention"), jax.named_scope("attn"):
            if decode:
                if cfg.attn == "mla":
                    a, cache = attn_mod.mla_decode(p["self_attention"], cfg, h,
                                                   cache, pos)
                else:
                    a, cache = attn_mod.gqa_decode(p["self_attention"], cfg, h,
                                                   cache, pos)
            else:
                if cfg.attn == "mla":
                    a = attn_mod.mla_forward(p["self_attention"], cfg, h,
                                             ctx=ctx)
                else:
                    a = attn_mod.gqa_forward(p["self_attention"], cfg, h,
                                             ctx=ctx, use_kernel=use_kernel)
        x = x + a
        with jax.named_scope("norm"):
            h = rmsnorm(p["post_attn_norm"], x)
        with ctx.scope("mlp"), jax.named_scope("mlp"):
            if kind == "attn_moe":
                mo, aux = moe_mod.moe_forward(p["mlp"], cfg, h, ctx=ctx)
            elif cfg.arch_type == "audio":
                mo = gelu_mlp(p["mlp"], h, ctx=ctx, precision=precision)
            else:
                mo = swiglu_mlp(p["mlp"], h, ctx=ctx, precision=precision)
        x = x + mo
        return x, aux, cache
    if kind == "rwkv":
        st = cache or {"time_mix": None, "channel_mix": None}
        h = rmsnorm(p["input_norm"], x)
        with ctx.scope("time_mix"):
            tm, new_tm = ssm_mod.rwkv6_time_mix(p["time_mix"], cfg, h, ctx=ctx,
                                                state=st["time_mix"])
        x = x + tm
        h = rmsnorm(p["post_tm_norm"], x)
        with ctx.scope("channel_mix"):
            cm, new_cm = ssm_mod.rwkv6_channel_mix(p["channel_mix"], cfg, h,
                                                   ctx=ctx,
                                                   state=st["channel_mix"])
        x = x + cm
        return x, aux, {"time_mix": new_tm, "channel_mix": new_cm}
    if kind == "mamba":
        h = rmsnorm(p["input_norm"], x)
        with ctx.scope("mixer"):
            mo, new_state = ssm_mod.mamba2_forward(p["mixer"], cfg, h, ctx=ctx,
                                                   state=cache)
        return x + mo, aux, new_state
    raise ValueError(kind)


def block_init_cache(cfg: ArchConfig, kind: str, batch, seq_len, dtype):
    if kind in ("attn_mlp", "attn_dense_mlp", "attn_moe", "shared_attn"):
        if cfg.attn == "mla":
            return attn_mod.mla_init_cache(cfg, batch, seq_len, dtype)
        return attn_mod.gqa_init_cache(cfg, batch, seq_len, dtype)
    if kind == "rwkv":
        return ssm_mod.rwkv6_init_state(cfg, batch, dtype)
    if kind == "mamba":
        return ssm_mod.mamba2_init_state(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.plan = build_plan(cfg)
        self.dtype = jnp.dtype(cfg.param_dtype)
        self.cdtype = jnp.dtype(cfg.compute_dtype)

    # ---- init ---------------------------------------------------------------
    def init(self, rng) -> dict:
        cfg = self.cfg
        keys = jax.random.split(rng, len(self.plan) + 4)
        params = {"embedding": {"word_embeddings":
                                embed_init(keys[0], cfg.vocab, cfg.d_model,
                                           self.dtype)},
                  "final_norm": jnp.ones((cfg.d_model,), self.dtype)}
        if not cfg.tie_embeddings:
            params["lm_head"] = embed_init(keys[1], cfg.vocab, cfg.d_model,
                                           self.dtype)
        if cfg.arch_type == "vlm":
            params["vision_proj"] = linear_init(keys[2], cfg.vision_dim,
                                                cfg.d_model, self.dtype,
                                                bias=True)
        if cfg.arch_type == "audio":
            params["audio_proj"] = linear_init(keys[2], cfg.audio_dim,
                                               cfg.d_model, self.dtype,
                                               bias=True)
            params["mask_embed"] = (0.02 * jax.random.normal(
                keys[3], (cfg.d_model,), jnp.float32)).astype(self.dtype)
        shared_done = False
        for seg, k in zip(self.plan, keys[4:]):
            if seg.shared:
                if not shared_done:
                    params["shared_attn"] = block_init(k, cfg, "shared_attn",
                                                       self.dtype)
                    shared_done = True
                continue
            if seg.scan:
                sub = jax.random.split(k, seg.n)
                params[seg.name] = jax.vmap(
                    lambda kk: block_init(kk, cfg, seg.kind, self.dtype))(sub)
            elif seg.n == 1:
                params[seg.name] = [block_init(k, cfg, seg.kind, self.dtype)]
            else:
                sub = jax.random.split(k, seg.n)
                params[seg.name] = [block_init(kk, cfg, seg.kind, self.dtype)
                                    for kk in sub]
        return params

    # ---- embedding / head ----------------------------------------------------
    def embed(self, params, batch, ctx=None):
        cfg = self.cfg
        ctx = ensure_ctx(ctx)
        with ctx.scope("embedding"), jax.named_scope("embed"):
            if cfg.arch_type == "audio":
                feats = batch["features"].astype(self.cdtype)
                h = linear(params["audio_proj"], feats)
                if "mask" in batch:
                    m = batch["mask"][..., None].astype(self.cdtype)
                    h = h * (1 - m) + params["mask_embed"].astype(self.cdtype) * m
            else:
                tok = params["embedding"]["word_embeddings"]
                h = tok[batch["tokens"]].astype(self.cdtype)
                if cfg.arch_type == "vlm" and "image_embeds" in batch:
                    img = linear(params["vision_proj"],
                                 batch["image_embeds"].astype(self.cdtype))
                    h = jnp.concatenate([img, h], axis=1)
            h = ctx.tap("output", h)
        h = constrain(h, "btd")
        return h

    def unembed(self, params, h):
        e = (params["embedding"]["word_embeddings"]
             if self.cfg.tie_embeddings else params["lm_head"])
        return _logits(h, e)

    # ---- forward --------------------------------------------------------------
    def apply_blocks(self, params, h, ctx=None, caches=None, pos=None,
                     decode=False, use_kernel=False, precision=None):
        cfg = self.cfg
        ctx = ensure_ctx(ctx)
        aux_total = jnp.zeros((), jnp.float32)
        new_caches = {}
        for seg in self.plan:
            cache = None if caches is None else caches.get(seg.name)
            p_seg = params["shared_attn"] if seg.shared else params[seg.name]
            if seg.shared or not seg.scan:
                blocks = [p_seg] if seg.shared else p_seg
                ncs = []
                for j, bp in enumerate(blocks):
                    li = seg.layer0 + j
                    scope = (f"{seg.name}" if seg.shared else f"layers.{li}")
                    bc = None if cache is None else cache[j]
                    with ctx.scope(scope):
                        h, aux, nc = block_apply(
                            bp, cfg, seg.kind, h, ctx, cache=bc, pos=pos,
                            decode=decode, use_kernel=use_kernel,
                            precision=precision)
                    h = constrain(h, "btd")
                    aux_total += aux
                    ncs.append(nc)
                new_caches[seg.name] = ncs
            else:
                def body(carry, xs):
                    hh, aux_c = carry
                    bp, bc = xs
                    hh, aux, nc = block_apply(bp, cfg, seg.kind, hh, None,
                                              cache=bc, pos=pos, decode=decode,
                                              use_kernel=use_kernel,
                                              precision=precision)
                    # note: no sharding constraint here — inside a rematted
                    # scan body the constrained copy of the carry would be
                    # saved ALONGSIDE the carry itself (2x activation saves);
                    # the carry inherits its layout from the scan entry.
                    return (hh, aux_c + aux), nc
                if cfg.remat and cfg.remat_policy == "dots":
                    fn = jax.checkpoint(
                        body, policy=jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable)
                elif cfg.remat:
                    fn = jax.checkpoint(body)
                else:
                    fn = body
                (h, aux_total), ncs = jax.lax.scan(
                    fn, (h, aux_total), (p_seg, cache))
                new_caches[seg.name] = ncs
        with jax.named_scope("norm"):
            h = rmsnorm(params["final_norm"], h)
        h = ctx.tap("final_norm_out", h) if ctx.mode != "off" else h
        return h, aux_total, new_caches

    def forward(self, params, batch, ctx=None, use_kernel=False,
                precision=None):
        h = self.embed(params, batch, ctx)
        h, aux, _ = self.apply_blocks(params, h, ctx, use_kernel=use_kernel,
                                      precision=precision)
        return h, aux

    # ---- loss -------------------------------------------------------------------
    def loss(self, params, batch, ctx=None, use_kernel=False, precision=None):
        cfg = self.cfg
        h, aux = self.forward(params, batch, ctx, use_kernel=use_kernel,
                              precision=precision)
        e = (params["embedding"]["word_embeddings"]
             if cfg.tie_embeddings else params.get("lm_head"))
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if cfg.arch_type == "vlm":
            h = h[:, -labels.shape[1]:]          # loss only on text positions
        if cfg.arch_type == "audio":
            mask = batch["mask"]
        big = h.shape[1] * cfg.vocab > (1 << 26) and not COST_MODE
        with jax.named_scope("loss"):
            if big:
                ce = chunked_cross_entropy(h, e, labels, mask=mask,
                                           chunk=min(1024, h.shape[1]))
            else:
                ce = cross_entropy(_logits(h, e), labels, mask=mask)
        loss = ce + aux
        return loss, {"ce": ce, "aux": aux}

    # ---- decode -------------------------------------------------------------------
    def init_cache(self, batch, seq_len, dtype=None):
        cfg = self.cfg
        dtype = dtype or self.cdtype
        caches = {}
        for seg in self.plan:
            if seg.shared:
                caches[seg.name] = [block_init_cache(cfg, "shared_attn", batch,
                                                     seq_len, dtype)]
            elif seg.scan:
                one = block_init_cache(cfg, seg.kind, batch, seq_len, dtype)
                caches[seg.name] = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (seg.n,) + x.shape),
                    one)
            else:
                caches[seg.name] = [block_init_cache(cfg, seg.kind, batch,
                                                     seq_len, dtype)
                                    for _ in range(seg.n)]
        return caches

    def decode_step(self, params, caches, tokens, pos, ctx=None):
        """tokens: (B,1) int32; pos: scalar int32.  Returns (logits, caches)."""
        batch = {"tokens": tokens}
        h = self.embed(params, batch, ctx)
        h, _, new_caches = self.apply_blocks(params, h, ctx, caches=caches,
                                             pos=pos, decode=True)
        logits = self.unembed(params, h)
        return logits, new_caches
