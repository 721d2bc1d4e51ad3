"""Plain reference of a dense Qwen-family decoder (Qwen2, Qwen3) and of
its AdamW training step, in ``jax.numpy`` with an explicit matmul
precision.  It imports nothing of the system under test.

Weights live in one flat ``{name: array}`` dict in the fused layout the
system under test also uses: ``layers.<i>.self_attention.linear_qkv.w`` is
``[Wq | Wk | Wv]`` along its output axis (heads in order), ``.b`` its bias
(Qwen2 only), ``linear_proj.w`` the output projection, ``mlp.{gate,up,down}.w``
the SwiGLU projections, ``{input,post_attn}_norm`` the two RMSNorm weights,
``q_norm``/``k_norm`` the per-head RMSNorms (Qwen3 only) and
``embedding.word_embeddings`` the token embedding, tied to the output head.

The layer equations are the published ones (Qwen2/Qwen3 modelling code)
with two departures, stated in each configuration file: RMSNorm epsilon
1e-5 (published 1e-6) and rotary embedding over interleaved pairs
``(x[2i], x[2i+1])`` instead of the two halves of a head.  Both are how
the system under test computes; neither changes a shape or a FLOP.

``c`` is the configuration file's ``config`` object (Hugging Face key
names).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5


def leaf_shapes(c: dict) -> dict:
    """``{name: shape}`` of every weight."""
    d, v = c["hidden_size"], c["vocab_size"]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    f = c["intermediate_size"]
    shapes = {"embedding.word_embeddings": (v, d), "final_norm": (d,)}
    for i in range(c["num_hidden_layers"]):
        p = f"layers.{i}."
        shapes[p + "input_norm"] = (d,)
        shapes[p + "post_attn_norm"] = (d,)
        shapes[p + "self_attention.linear_qkv.w"] = (d, (h + 2 * hkv) * hd)
        if c.get("qkv_bias"):
            shapes[p + "self_attention.linear_qkv.b"] = ((h + 2 * hkv) * hd,)
        shapes[p + "self_attention.linear_proj.w"] = (h * hd, d)
        if c.get("qk_norm"):
            shapes[p + "self_attention.q_norm"] = (hd,)
            shapes[p + "self_attention.k_norm"] = (hd,)
        shapes[p + "mlp.gate.w"] = (d, f)
        shapes[p + "mlp.up.w"] = (d, f)
        shapes[p + "mlp.down.w"] = (f, d)
    return shapes


def init_weights(key, c: dict) -> dict:
    """Weights from ``key``: matrices and biases N(0, 0.02^2), norm weights
    1 + N(0, 0.02^2).  Call it under ``jax.jit``."""
    shapes = leaf_shapes(c)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        x = 0.02 * jax.random.normal(k, shape, jnp.float32)
        out[name] = 1.0 + x if name.endswith("norm") else x
    return out


def _einsum_3pass(eq, a, b):
    """``einsum`` at the precision XLA calls HIGH: each float32 operand
    split into a bfloat16 head and tail, and the three products that
    matter (head x head, head x tail, tail x head) summed in float32."""
    f = lambda x, y: jnp.einsum(eq, x, y,
                                preferred_element_type=jnp.float32)
    ah = a.astype(jnp.bfloat16)
    al = (a - ah.astype(jnp.float32)).astype(jnp.bfloat16)
    bh = b.astype(jnp.bfloat16)
    bl = (b - bh.astype(jnp.float32)).astype(jnp.bfloat16)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def einsum_3pass(eq, a, b):
    """Three-pass ``einsum`` whose gradients are three-pass products too,
    on any device (the backward of the split would otherwise lose the
    tail).  Each operand's indices must appear in the other two."""
    return _einsum_3pass(eq, a, b)


def _3pass_fwd(eq, a, b):
    return _einsum_3pass(eq, a, b), (a, b)


def _3pass_bwd(eq, res, dy):
    a, b = res
    ins, out = eq.split("->")
    sa, sb = ins.split(",")
    return (_einsum_3pass(f"{out},{sb}->{sa}", dy, b),
            _einsum_3pass(f"{sa},{out}->{sb}", a, dy))


einsum_3pass.defvjp(_3pass_fwd, _3pass_bwd)


def _rmsnorm(w, x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * w


def _rope(x, theta):
    """x: (B, S, H, D); rotates the interleaved pairs (2i, 2i+1)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv     # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def loss(w: dict, tokens, labels, c: dict, precision, mask=None):
    """Mean next-token cross-entropy over the tokens ``mask`` keeps (all
    when None).  ``precision`` is a ``jax.lax.Precision`` or ``"3pass"``
    (``einsum_3pass``)."""
    if precision == "3pass":
        mm = einsum_3pass
    else:
        mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=precision)
    h_, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    g = h_ // hkv
    b, s = tokens.shape
    x = w["embedding.word_embeddings"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(c["num_hidden_layers"]):
        p = f"layers.{i}."
        a = _rmsnorm(w[p + "input_norm"], x)
        qkv = mm("bsd,de->bse", a, w[p + "self_attention.linear_qkv.w"])
        if c.get("qkv_bias"):
            qkv = qkv + w[p + "self_attention.linear_qkv.b"]
        q = qkv[..., :h_ * hd].reshape(b, s, h_, hd)
        k = qkv[..., h_ * hd:(h_ + hkv) * hd].reshape(b, s, hkv, hd)
        v = qkv[..., (h_ + hkv) * hd:].reshape(b, s, hkv, hd)
        if c.get("qk_norm"):
            q = _rmsnorm(w[p + "self_attention.q_norm"], q)
            k = _rmsnorm(w[p + "self_attention.k_norm"], k)
        q = _rope(q, c["rope_theta"]).reshape(b, s, hkv, g, hd)
        k = _rope(k, c["rope_theta"])
        sc = mm("bqhgd,bkhd->bhgqk", q, k) / jnp.sqrt(jnp.float32(hd))
        sc = jnp.where(causal, sc, -1e30)
        o = mm("bhgqk,bkhd->bqhgd", jax.nn.softmax(sc, axis=-1), v)
        x = x + mm("bse,ed->bsd", o.reshape(b, s, h_ * hd),
                   w[p + "self_attention.linear_proj.w"])
        a = _rmsnorm(w[p + "post_attn_norm"], x)
        u = (jax.nn.silu(mm("bsd,df->bsf", a, w[p + "mlp.gate.w"]))
             * mm("bsd,df->bsf", a, w[p + "mlp.up.w"]))
        x = x + mm("bsf,fd->bsd", u, w[p + "mlp.down.w"])
    x = _rmsnorm(w["final_norm"], x)
    logits = mm("bsd,vd->bsv", x, w["embedding.word_embeddings"])
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0])
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.sum(mask)


def adamw_init(w: dict) -> dict:
    z = {k: jnp.zeros_like(x) for k, x in w.items()}
    return {"m": z, "v": dict(z), "t": jnp.zeros((), jnp.float32)}


def _decayed(name: str) -> bool:
    """Weight decay on matrices and the embedding; not on norms or
    biases."""
    last = name.rsplit(".", 1)[-1]
    return not (last.endswith("norm") or last == "b")


def train_step(w, st, tokens, labels, c: dict, opt: dict, precision,
               mask=None):
    """One AdamW step: returns ``(loss, grads as the optimizer gets them
    (after global-norm clipping), new weights, new state)``."""
    lval, gr = jax.value_and_grad(loss)(w, tokens, labels, c, precision,
                                        mask)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in gr.values()))
    scale = jnp.minimum(1.0, opt["clip"] / jnp.maximum(norm, 1e-12))
    gr = {k: g * scale for k, g in gr.items()}
    t = st["t"] + 1.0
    b1, b2 = opt["b1"], opt["b2"]
    m = {k: b1 * st["m"][k] + (1 - b1) * gr[k] for k in w}
    v = {k: b2 * st["v"][k] + (1 - b2) * gr[k] * gr[k] for k in w}
    new = {}
    for k in w:
        u = (m[k] / (1 - b1 ** t)) / (jnp.sqrt(v[k] / (1 - b2 ** t))
                                      + opt["eps"])
        if _decayed(k):
            u = u + opt["weight_decay"] * w[k]
        new[k] = w[k] - opt["lr"] * u
    return lval, gr, new, {"m": m, "v": v, "t": t}
