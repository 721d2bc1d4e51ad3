"""AdamW with fp32 master weights and main gradients.

Mirrors Megatron's mixed-precision distributed optimizer semantics, which is
what TTrace instruments (paper §4.3):

* model params may be bf16; the optimizer holds an **fp32 master copy**;
* incoming grads are upcast and accumulated in fp32 — the **main gradients**
  TTrace traces right before the step;
* the update runs entirely in fp32 and the model params are re-cast from the
  masters — the **post-step parameters** TTrace traces right after the step.

``update`` returns an ``OptInfo`` carrying both trace bundles so the TTrace
collector never has to reach into optimizer internals.
ZeRO-1 sharding of this state lives in repro/parallel/zero.py.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp


def _reshard_like_opt_state(grads):
    """Under an active GSPMD sharding context, reshard incoming grads to the
    (ZeRO-style data-densified) optimizer-state layout BEFORE the fp32
    upcast — otherwise the fp32 main grads materialize at the params'
    model-only sharding (e.g. 27 GiB/device for qwen1.5-110b; §Perf)."""
    from repro.sharding import rules
    ctx = rules.current()
    if ctx is None:
        return grads
    from repro.core.collector import flatten_named, unflatten_named
    named = flatten_named(grads)
    sh = rules.param_shardings({k: v.shape for k, v in named.items()},
                               ctx.mesh, opt_state=True)
    return unflatten_named(
        {k: jax.lax.with_sharding_constraint(v, sh[k])
         for k, v in named.items()}, grads)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def warmup_cosine(base_lr: float, warmup: int, total: int,
                  min_ratio: float = 0.1) -> Callable:
    def lr(step):
        step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
        w = jnp.minimum(1.0, (step + 1) / max(warmup, 1))
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = min_ratio + (1 - min_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return base_lr * w * cos
    return lr


@jax.tree_util.register_dataclass
@dataclass
class OptInfo:
    loss_scale: jax.Array
    grad_norm: jax.Array
    lr: jax.Array
    main_grads: Any      # fp32 grads after clipping — TTrace "main gradients"
    pre_clip_norm: jax.Array


@dataclass
class AdamW:
    lr: float | Callable = 1e-3
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    clip: float = 1.0
    # parameters whose path matches any of these suffixes skip weight decay
    no_decay_suffixes: tuple = ("norm", "b", "bias", "mu", "u", "w0", "D",
                                "A_log", "dt_bias", "mu_x", "mu_k", "mu_r")

    def init(self, params):
        f32 = lambda t: jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), t)
        master = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        # both moments start as the same zeros: arrays are immutable and no
        # step donates them, so one buffer per leaf serves both until the
        # first update (a model's worth of device memory at full width)
        zeros = f32(params)
        return {"master": master, "m": zeros, "v": zeros,
                "step": jnp.zeros((), jnp.int32)}

    def _decay_mask(self, params):
        paths = jax.tree_util.tree_flatten_with_path(params)[0]

        def leaf_decay(path):
            last = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
            return not any(last == s or last.endswith("_norm") or
                           last.startswith("mu") or last in ("b",)
                           for s in self.no_decay_suffixes)
        flat = [leaf_decay(p) for p, _ in paths]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params), flat)

    def update(self, params, grads, state, loss_scale=None):
        # the named scope labels the update's operations in a profile
        with jax.named_scope("optimizer"):
            return self._update(params, grads, state, loss_scale)

    def _update(self, params, grads, state, loss_scale):
        step = state["step"] + 1
        lr = self.lr(state["step"]) if callable(self.lr) else jnp.float32(self.lr)
        grads = _reshard_like_opt_state(grads)
        main_grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if loss_scale is not None:
            main_grads = jax.tree.map(lambda g: g / loss_scale, main_grads)
        pre_norm = global_norm(main_grads)
        if self.clip:
            scale = jnp.minimum(1.0, self.clip / jnp.maximum(pre_norm, 1e-12))
            main_grads = jax.tree.map(lambda g: g * scale, main_grads)
        gnorm = global_norm(main_grads)

        decay = self._decay_mask(params)
        b1, b2 = self.b1, self.b2
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(master, g, m, v, dec):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m / bc1) / (jnp.sqrt(v / bc2) + self.eps)
            if self.weight_decay:
                u = u + jnp.where(dec, self.weight_decay, 0.0) * master
            return master - lr * u, m, v

        new = jax.tree.map(upd, state["master"], main_grads, state["m"],
                           state["v"], decay)
        master = jax.tree.map(lambda t: t[0], new, is_leaf=lambda t: isinstance(t, tuple))
        m = jax.tree.map(lambda t: t[1], new, is_leaf=lambda t: isinstance(t, tuple))
        v = jax.tree.map(lambda t: t[2], new, is_leaf=lambda t: isinstance(t, tuple))
        new_params = jax.tree.map(lambda mast, p: mast.astype(p.dtype),
                                  master, params)
        info = OptInfo(loss_scale=jnp.float32(loss_scale or 1.0),
                       grad_norm=gnorm, lr=jnp.float32(lr),
                       main_grads=main_grads, pre_clip_norm=pre_norm)
        return new_params, {"master": master, "m": m, "v": v, "step": step}, info
