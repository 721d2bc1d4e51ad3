"""Manual tensor/sequence/context-parallel layers (shard_map bodies).

These are the Megatron-style hand-written distributed layers — explicit
``psum`` / ``all_gather`` / ``psum_scatter`` / ``ppermute`` collectives on a
("dp", "cp", "tp") mesh — i.e. the *candidate* side of TTrace's differential
test.  Every function takes ``bugs`` (frozenset of ids from
repro.bugs.registry) and injects the corresponding silent bug when asked:
this file is where Table 1's bug taxonomy lives.

All functions run INSIDE a shard_map body; "local" means per-device shard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tap import ensure_ctx
from repro.models.attention import NEG_INF, attention_ref
from repro.models.layers import apply_rope, rmsnorm

AX_DP, AX_CP, AX_TP = "dp", "cp", "tp"


def axis_size(name):
    """Static size of a mesh axis; 1 outside a shard_map that binds it."""
    try:
        return jax.lax.axis_size(name)
    except NameError:
        return 1


def axis_index(name):
    return jax.lax.axis_index(name)


# ---------------------------------------------------------------------------
# Megatron's conjugate communication operators (f / g).
#
# Under shard_map with unchecked replication, a bare ``psum`` does not know
# whether its cotangent is replicated, so AD through it double-counts.  The
# classic fix — exactly what Megatron's ``copy_to_tensor_model_parallel_region``
# and ``reduce_from_tensor_model_parallel_region`` do — is a conjugate pair:
#   g_copy:   identity forward, psum backward   (enter column-parallel compute)
#   g_reduce: psum forward, identity backward   (leave row-parallel compute)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def g_copy(x):
    return x


def _g_copy_fwd(x):
    return x, None


def _g_copy_bwd(_, g):
    return (jax.lax.psum(g, AX_TP),)


g_copy.defvjp(_g_copy_fwd, _g_copy_bwd)


@jax.custom_vjp
def g_reduce(x):
    return jax.lax.psum(x, AX_TP)


def _g_reduce_fwd(x):
    return jax.lax.psum(x, AX_TP), None


def _g_reduce_bwd(_, g):
    return (g,)


g_reduce.defvjp(_g_reduce_fwd, _g_reduce_bwd)


def g_reduce_over(x, axes):
    """psum-forward / identity-backward over arbitrary axes (the conjugate
    reduce for cross-rank statistics like the MoE load-balance stats)."""
    if not axes:
        return x

    @jax.custom_vjp
    def f(x):
        return jax.lax.psum(x, axes)

    def fwd(x):
        return jax.lax.psum(x, axes), None

    def bwd(_, g):
        return (g,)

    f.defvjp(fwd, bwd)
    return f(x)


# ---------------------------------------------------------------------------
# Zigzag context-parallel layout helpers (paper Fig 6: striped attention)
# ---------------------------------------------------------------------------

def zigzag_order(cp: int) -> list[int]:
    """Chunk order such that contiguous rank splits give zigzag stripes:
    rank r owns chunks (r, 2cp-1-r)."""
    out = []
    for r in range(cp):
        out += [r, 2 * cp - 1 - r]
    return out


def zigzag_index(seq: int, cp: int) -> np.ndarray:
    """Logical sequence positions in zigzag layout order: contiguous rank
    splits of the permuted axis give rank r the chunks (r, 2cp-1-r)."""
    chunks = np.arange(seq).reshape(2 * cp, -1)
    return np.concatenate([chunks[c] for c in zigzag_order(cp)])


def permute_to_zigzag(x, cp: int, dim: int):
    """Logical -> zigzag order along ``dim``: one gather with a static
    index, so it traces inside jit on sharded inputs."""
    if cp == 1:
        return x
    return jnp.take(x, zigzag_index(x.shape[dim], cp), axis=dim)


def permute_from_zigzag(x, cp: int, dim: int):
    if cp == 1:
        return x
    return jnp.take(x, np.argsort(zigzag_index(x.shape[dim], cp)), axis=dim)


def local_positions(seq_global: int, cp: int):
    """Absolute token positions of this rank's zigzag stripes (traced)."""
    if cp == 1:
        return jnp.arange(seq_global)
    r = axis_index(AX_CP)
    chunk = seq_global // (2 * cp)
    a = r * chunk + jnp.arange(chunk)
    b = (2 * cp - 1 - r) * chunk + jnp.arange(chunk)
    return jnp.concatenate([a, b])


# ---------------------------------------------------------------------------
# Vocab-parallel embedding (bug 1 lives here)
# ---------------------------------------------------------------------------

def vocab_parallel_embedding(w_local, tokens, vocab: int, bugs=frozenset(),
                             reduce: str = "psum"):
    """w_local: (V/tp, d) — this rank's vocab rows.  Wrong ownership mask
    (``tp_wrong_embedding_mask``) lets boundary tokens be embedded by two
    ranks and double-counted by the all-reduce — paper bug 1.

    ``reduce``: "psum" (full output) or "scatter" (sequence-parallel:
    reduce-scatter along seq, output (B, S/tp, d))."""
    tp = axis_size(AX_TP)
    per = vocab // tp
    start = axis_index(AX_TP) * per
    if "tp_wrong_embedding_mask" in bugs:
        # wrong upper bound: this rank also claims the next rank's lower
        # half; those tokens hit the clipped last row AND get double-counted
        # by the all-reduce (paper bug 1: wrong forward + gradients)
        own = (tokens >= start) & (tokens < start + per + per // 2)
    else:
        own = (tokens >= start) & (tokens < start + per)
    local_idx = jnp.clip(tokens - start, 0, per - 1)
    emb = w_local[local_idx]
    emb = jnp.where(own[..., None], emb, 0.0)
    if reduce == "scatter":
        return jax.lax.psum_scatter(emb, AX_TP, scatter_dimension=1,
                                    tiled=True)
    return g_reduce(emb)


# ---------------------------------------------------------------------------
# Column / row parallel linears
# ---------------------------------------------------------------------------

def column_linear(p_local, x):
    """weights sharded on the OUTPUT dim; no forward comm."""
    y = x @ p_local["w"].astype(x.dtype)
    if "b" in p_local:
        y = y + p_local["b"].astype(x.dtype)
    return y


def one_rank(x, axis):
    """Model a missing/wrong collective silently: in the real framework every
    rank keeps its own (conflicting) partial value — the paper's "conflicting
    tensor".  Our single-trace runner takes rank 0's partial so the result is
    one consistent, silently-wrong value."""
    return jax.lax.all_gather(x, axis, axis=0)[0]


def row_linear(p_local, x_local, bugs=frozenset(), reduce_out=True,
               bug_axis_id="tp_wrong_allreduce_axis",
               bug_missing_id="tp_missing_row_psum"):
    """weights sharded on the INPUT dim; output needs a psum over tp.

    Bugs: wrong all-reduce group (psum over dp — paper bug 7 analogue) or a
    missing all-reduce (partial sums downstream — paper bugs 6/11 class)."""
    y = x_local @ p_local["w"].astype(x_local.dtype)
    if reduce_out:
        if bug_missing_id in bugs:
            y = one_rank(y, AX_TP)                # M-CM: forgot the psum
        elif bug_axis_id in bugs:
            y = jax.lax.psum(y, AX_DP)            # W-CM: wrong group
            y = one_rank(y, AX_TP)
        else:
            y = g_reduce(y)
    if "b" in p_local:
        y = y + p_local["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Sequence parallelism (gather/scatter along seq over the tp axis)
# ---------------------------------------------------------------------------

def sp_gather(x, dim=1):
    return jax.lax.all_gather(x, AX_TP, axis=dim, tiled=True)


def sp_scatter(x, dim=1):
    return jax.lax.psum_scatter(x, AX_TP, scatter_dimension=dim, tiled=True)


# ---------------------------------------------------------------------------
# Context-parallel attention (zigzag stripes; KV all-gather)
# ---------------------------------------------------------------------------

def _cp_attention_math(q, k, v, q_pos, k_pos):
    B, Q, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Q, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(D).astype(jnp.float32)
    mask = k_pos[None, :] <= q_pos[:, None]
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Q, H, D).astype(q.dtype)


def cp_attention(q, k, v, q_pos, bugs=frozenset()):
    """q,k,v local zigzag stripes (B, S/cp, H_local, D); gathers K/V over cp.

    ``cp_wrong_attention_grad`` (paper bug 13): forward is correct but the
    backward uses the FIRST stripe's positions for both stripes, dropping the
    second stripe's causal-mask correction."""
    cp = axis_size(AX_CP)
    if cp == 1:
        return _cp_attention_math(q, k, v, q_pos, q_pos)
    kg = jax.lax.all_gather(k, AX_CP, axis=1, tiled=True)
    vg = jax.lax.all_gather(v, AX_CP, axis=1, tiled=True)
    k_pos = jax.lax.all_gather(q_pos, AX_CP, axis=0, tiled=True)

    if "cp_wrong_attention_grad" not in bugs:
        return _cp_attention_math(q, kg, vg, q_pos, k_pos)

    half = q_pos.shape[0] // 2
    bad_q_pos = jnp.concatenate([q_pos[:half], q_pos[:half]])

    @jax.custom_vjp
    def buggy(q, kg, vg):
        return _cp_attention_math(q, kg, vg, q_pos, k_pos)

    def fwd(q, kg, vg):
        return buggy(q, kg, vg), (q, kg, vg)

    def bwd(res, g):
        q, kg, vg = res
        _, vjp = jax.vjp(
            lambda a, b, c: _cp_attention_math(a, b, c, bad_q_pos, k_pos),
            q, kg, vg)
        return vjp(g)

    buggy.defvjp(fwd, bwd)
    return buggy(q, kg, vg)


# ---------------------------------------------------------------------------
# TP attention block (heads sharded over tp)
# ---------------------------------------------------------------------------

def tp_gqa_attention(p_local, cfg, x, q_pos, sp: bool, bugs=frozenset(),
                     ctx=None):
    """x: (B, S_local, d_model) — seq local under SP/CP, else full.
    Head-parallel attention with fused column-parallel linear_qkv and
    row-parallel linear_proj."""
    ctx = ensure_ctx(ctx)
    x = ctx.tap("input", x)
    tp = axis_size(AX_TP)
    H, Hkv, D = cfg.n_heads // tp, cfg.n_kv_heads // tp, cfg.d_head
    if sp:
        x = sp_gather(x)          # attention region runs on the full sequence
    elif tp > 1:
        x = g_copy(x)             # enter column-parallel compute
    B, S, _ = x.shape
    qkv = column_linear(p_local["linear_qkv"], x)
    q, k, v = jnp.split(qkv, [H * D, (H + Hkv) * D], axis=-1)
    q = q.reshape(B, S, H, D)
    k = k.reshape(B, S, Hkv, D)
    v = v.reshape(B, S, Hkv, D)
    if cfg.qk_norm:
        q = rmsnorm(p_local["q_norm"], q)
        k = rmsnorm(p_local["k_norm"], k)
    pos_b = jnp.broadcast_to(q_pos, (B,) + q_pos.shape)
    q = apply_rope(q, pos_b, cfg.rope_theta)
    k = apply_rope(k, pos_b, cfg.rope_theta)
    o = cp_attention(q, k, v, q_pos, bugs=bugs)
    o = o.reshape(B, S, H * D)
    o = ctx.tap("core_attn_out", o)
    pp = p_local["linear_proj"]
    if sp:
        yl = _matmul(o, pp["w"], stale_wgrad="sp_stale_wgrad" in bugs)
        y = jax.lax.psum_scatter(yl, AX_TP, scatter_dimension=1, tiled=True)
        if "b" in pp:
            y = y + pp["b"].astype(y.dtype)
    else:
        y = row_linear(pp, o, bugs=bugs,
                       bug_missing_id="attn_missing_row_psum")
    return ctx.tap("output", y)


def _matmul(o, w, stale_wgrad=False):
    """o @ w; with ``stale_wgrad`` (paper bug 11 — wrong gradients with
    comm/compute overlap) the forward and dgrad are correct but dW is
    computed from a half-zeroed activation, as if the overlapped backward
    all-gather returned a stale buffer."""
    if not stale_wgrad:
        return o @ w.astype(o.dtype)

    @jax.custom_vjp
    def f(o, w):
        return o @ w.astype(o.dtype)

    def fwd(o, w):
        return f(o, w), (o, w)

    def bwd(res, g):
        o, w = res
        do = g @ w.astype(g.dtype).T
        S = o.shape[1]
        o_stale = jnp.concatenate(
            [o[:, :S // 2], jnp.zeros_like(o[:, S // 2:])], axis=1)
        dw = jnp.einsum("bsi,bso->io", o_stale.astype(jnp.float32),
                        g.astype(jnp.float32)).astype(w.dtype)
        return do, dw
    f.defvjp(fwd, bwd)
    return f(o, w)


# ---------------------------------------------------------------------------
# TP MLP (column gate/up, row down)
# ---------------------------------------------------------------------------

def tp_swiglu_mlp(p_local, x, sp: bool, bugs=frozenset(), ctx=None):
    ctx = ensure_ctx(ctx)
    x = ctx.tap("input", x)
    if sp:
        x = sp_gather(x)
    elif axis_size(AX_TP) > 1:
        x = g_copy(x)
    h = (jax.nn.silu(column_linear(p_local["gate"], x))
         * column_linear(p_local["up"], x))
    y = _maybe_stale_recompute(h, bugs)
    if sp:
        yl = y @ p_local["down"]["w"].astype(y.dtype)
        out = jax.lax.psum_scatter(yl, AX_TP, scatter_dimension=1, tiled=True)
    else:
        out = row_linear(p_local["down"], y, bugs=bugs,
                         bug_axis_id="mlp_wrong_allreduce_axis")
    return ctx.tap("output", out)


def _maybe_stale_recompute(h, bugs):
    """``ar_stale_recompute`` (paper bug 2): activation recomputation uses an
    outdated input — forward is right, the backward sees a token-shifted h."""
    if "ar_stale_recompute" not in bugs:
        return h

    @jax.custom_vjp
    def f(h):
        return h

    def fwd(h):
        return h, (h,)

    def bwd(res, g):
        (h,) = res
        return (jnp.roll(g, 1, axis=1),)   # grad routed to shifted positions
    f.defvjp(fwd, bwd)
    return f(h)


# ---------------------------------------------------------------------------
# Vocab-parallel cross entropy
# ---------------------------------------------------------------------------

def vocab_parallel_ce(logits_local, labels, vocab: int):
    """logits_local: (B, S_local, V/tp).  Max/sumexp/gold psum'ed over tp.
    Returns per-token nll (B, S_local)."""
    tp = axis_size(AX_TP)
    per = vocab // tp
    start = axis_index(AX_TP) * per
    lf = logits_local.astype(jnp.float32)
    # max is a constant shift for stability — detach it (pmax has no AD rule;
    # the gradient is exact anyway since the shift cancels in lse - gold)
    m = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(lf, axis=-1)), AX_TP)
    se = g_reduce(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1))
    lse = jnp.log(se) + m
    own = (labels >= start) & (labels < start + per)
    lidx = jnp.clip(labels - start, 0, per - 1)
    gold_local = jnp.take_along_axis(lf, lidx[..., None], axis=-1)[..., 0]
    gold = g_reduce(jnp.where(own, gold_local, 0.0))
    return lse - gold
