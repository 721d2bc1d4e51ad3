"""Distributed GPT/Llama/MoE candidate model (manual collectives).

Mirrors the reference ``repro.models.model.Model`` tap-for-tap: the same
canonical module names, the same block structure — but built from the
manual-parallel layers so TP/SP/CP/EP silent bugs have somewhere to live.
Runs inside a shard_map body on a ("dp","cp","tp") mesh.

Supports the paper's evaluation families: dense GPT/Llama blocks and MoE
blocks (top-k router + expert parallelism over the tp axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.tap import ensure_ctx
from repro.models.layers import rmsnorm
from repro.models.moe import router_topk
from repro.parallel.layers import (
    AX_CP, AX_DP, AX_TP, axis_index, axis_size, g_copy, g_reduce,
    g_reduce_over, local_positions, sp_gather, tp_gqa_attention,
    tp_swiglu_mlp, vocab_parallel_ce, vocab_parallel_embedding,
)
from repro.models.moe import load_balance_loss


# ---------------------------------------------------------------------------
# Expert-parallel MoE (experts sharded over the tp axis)
# ---------------------------------------------------------------------------

def tp_moe(p_local, cfg: ArchConfig, x, sp: bool, bugs=frozenset(),
           ctx=None):
    """Router replicated; experts sharded over tp.  Each rank routes ALL
    (local-sequence) tokens, processes the ones assigned to its local
    experts, and the outputs are summed over tp.

    ``moe_router_not_synced`` (paper bug 6): the router weights differ per
    rank (missed broadcast at init) so ranks disagree about routing."""
    ctx = ensure_ctx(ctx)
    x = ctx.tap("input", x)
    if sp:
        x = sp_gather(x)
    elif axis_size(AX_TP) > 1:
        x = g_copy(x)
    m = cfg.moe
    tp = axis_size(AX_TP)
    El = m.n_experts // tp
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)

    router = p_local["router"]
    if "moe_router_not_synced" in bugs:
        # per-rank drift: the weights each rank *thinks* are synced
        r = axis_index(AX_TP).astype(jnp.float32)
        router = router * (1.0 + 0.05 * r)
    logits = xt.astype(jnp.float32) @ router
    logits = ctx.tap("router_logits",
                     logits.reshape(B, S, -1)).reshape(T, -1)
    top_p, top_e = router_topk(logits, m.top_k)

    from repro.models.moe import expert_capacity
    cap = expert_capacity(T, m)
    k = m.top_k
    flat_e = top_e.reshape(T * k)
    flat_w = top_p.reshape(T * k)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_e, stable=True)
    se, sw, stok = flat_e[order], flat_w[order], flat_tok[order]
    start = jnp.searchsorted(se, jnp.arange(m.n_experts), side="left")
    pos = jnp.arange(T * k) - start[se]
    e0 = axis_index(AX_TP) * El
    local = (se >= e0) & (se < e0 + El) & (pos < cap)
    le = jnp.where(local, se - e0, 0)
    lp = jnp.where(local, pos, 0)

    buf = jnp.zeros((El, cap, d), x.dtype)
    buf = buf.at[le, lp].add(jnp.where(local[:, None], xt[stok], 0.0
                                       ).astype(x.dtype))
    e = p_local["experts"]
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, e["gate"].astype(x.dtype)))
         * jnp.einsum("ecd,edf->ecf", buf, e["up"].astype(x.dtype)))
    out_buf = jnp.einsum("ecf,efd->ecd", h, e["down"].astype(x.dtype))
    gathered = out_buf[le, lp]
    contrib = jnp.where(local[:, None],
                        gathered.astype(jnp.float32) * sw[:, None], 0.0)
    yt = jnp.zeros((T, d), jnp.float32).at[stok].add(contrib)
    y = yt.reshape(B, S, d).astype(x.dtype)           # local-expert partials
    if sp:
        y = jax.lax.psum_scatter(y, AX_TP, scatter_dimension=1, tiled=True)
    else:
        y = g_reduce(y)                               # combine expert shards
    y = ctx.tap("output", y)
    # Load-balance statistics.  Divided by tp so that, like the dispatch
    # path, each rank holds a PARTIAL contribution: the caller reduces over
    # (dp, cp, tp) with a conjugate psum, which makes both the router-grad
    # all-reduce and the router_logits probe-gradient psum exact.
    probs = jax.nn.softmax(logits, axis=-1)
    count = jnp.zeros((m.n_experts,), jnp.float32).at[
        top_e.reshape(-1)].add(1.0)
    stats = {"probs_sum": probs.sum(0) / tp, "count": count / tp,
             "n_tokens": jnp.float32(T) / tp}
    return y, stats


# ---------------------------------------------------------------------------
# Full model body
# ---------------------------------------------------------------------------

def parallel_block(p, cfg: ArchConfig, x, q_pos, li: int, sp: bool,
                   moe: bool, bugs, ctx):
    ctx = ensure_ctx(ctx)
    with ctx.scope(f"layers.{li}"):
        # named scopes label the compiled program's operations by block,
        # as in the reference model
        with jax.named_scope("norm"):
            h = rmsnorm(p["input_norm"], x)
        with ctx.scope("self_attention"), jax.named_scope("attn"):
            a = tp_gqa_attention(p["self_attention"], cfg, h, q_pos, sp,
                                 bugs=bugs, ctx=ctx)
        x = x + a
        with jax.named_scope("norm"):
            h = rmsnorm(p["post_attn_norm"], x)
        stats = None
        with ctx.scope("mlp"), jax.named_scope("mlp"):
            if moe:
                mo, stats = tp_moe(p["mlp"], cfg, h, sp, bugs=bugs, ctx=ctx)
            else:
                mo = tp_swiglu_mlp(p["mlp"], h, sp, bugs=bugs, ctx=ctx)
        x = x + mo
    return x, stats


def parallel_gpt_loss(params, batch, cfg: ArchConfig, sp: bool,
                      bugs=frozenset(), ctx=None):
    """Returns (grad_loss, report_loss): ``grad_loss`` follows the explicit
    dp/cp gradient-averaging convention (aux pre-multiplied by dp*cp);
    ``report_loss`` is this rank's true local loss (ce_mean + aux).
    Runs inside shard_map; ``batch`` tokens/labels are (B_local, S_local)
    zigzag-layout shards."""
    ctx = ensure_ctx(ctx)
    tokens, labels = batch["tokens"], batch["labels"]
    cp = axis_size(AX_CP)
    S_local = tokens.shape[1]
    S_global = S_local * cp
    q_pos = local_positions(S_global, cp)

    with ctx.scope("embedding"), jax.named_scope("embed"):
        h = vocab_parallel_embedding(
            params["embedding"]["word_embeddings"], tokens, cfg.vocab,
            bugs=bugs, reduce="scatter" if sp else "psum")
        h = h.astype(jnp.dtype(cfg.compute_dtype))
        h = ctx.tap("output", h)

    moe = cfg.moe is not None
    all_stats = []
    for li, p in enumerate(params["layers"]):
        h, stats = parallel_block(p, cfg, h, q_pos, li, sp, moe, bugs, ctx)
        if stats is not None:
            all_stats.append(stats)

    with jax.named_scope("norm"):
        h = rmsnorm(params["final_norm"], h)
    h = ctx.tap("final_norm_out", h)
    if sp:
        h = sp_gather(h)
    elif axis_size(AX_TP) > 1:
        h = g_copy(h)
    e = (params["embedding"]["word_embeddings"] if cfg.tie_embeddings
         else params["lm_head"])
    with jax.named_scope("loss"):
        logits_local = h @ e.T.astype(h.dtype)        # (B, S_loc, V/tp)
        nll = vocab_parallel_ce(logits_local, labels, cfg.vocab)
        ce = jnp.mean(nll)

    # router load-balance aux loss from GLOBAL statistics: stats are summed
    # across dp/cp with a conjugate reduce so each rank's backward receives
    # its own piece of the global gradient.  The (dp*cp) factor compensates
    # the caller's explicit psum/(dp*cp) gradient averaging.
    if all_stats:
        axes = tuple(a for a in ("dp", "cp", "tp") if axis_size(a) > 1)
        dpcp = axis_size(AX_DP) * axis_size(AX_CP)
        aux = jnp.zeros((), jnp.float32)
        m = cfg.moe
        for st in all_stats:
            ps = g_reduce_over(st["probs_sum"], axes)
            cn = g_reduce_over(st["count"], axes)
            n_g = g_reduce_over(st["n_tokens"], axes)
            aux += load_balance_loss(ps / n_g, cn / (n_g * m.top_k),
                                     m.n_experts) * m.router_aux_coef
        return ce + aux * dpcp, ce + aux
    return ce, ce
