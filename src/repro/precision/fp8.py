"""FP8 (e4m3) training recipes + the stale-scale silent bug (paper §6.7, bug 8).

FP8 matmuls quantize operands to float8_e4m3fn with an amax-derived scale and
accumulate in >= bf16 — so, as the paper observes, the machine epsilon that
governs the *threshold estimation* is still BF16's.  Three scaling recipes
are modelled (paper §6.7):

  * "global":      one scale for the whole tensor (TransformerEngine default)
  * "per_tensor":  alias of global here (per-operand scale)
  * "tile128":     one scale per 128x128 tile (the DeepSeek-V3 recipe) —
                   finer granularity, smaller round-off, as §6.7 predicts.

``fp8_linear`` drops into the reference/parallel MLPs when a ``Precision``
recipe asks for it (``models.layers`` threads it through the model); the
Pallas kernels (repro/kernels/fp8_matmul) are the TPU execution path for the
same math — a plain fp8 matmul with the global scale folded outside, and a
tile-scaled variant that applies the per-128-tile scales inside the K loop.

``make_fp8_train_step`` / ``make_fp8_runner`` are the supervisor-facing
candidate factories: the candidate trains the SAME model with FP8 MLP
matmuls against the full-precision reference, checked under BF16-epsilon
thresholds (§6.7).

Bug 8 ("AR: wrong tensor by FP8 cast"): quantization uses a STALE amax — the
scale of the previous microbatch's tensor — modelled by halving the amax:
values clip, the loss is silently wrong.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
F8 = jnp.float8_e4m3fn
TILE = 128
FP8_RECIPES = ("global", "per_tensor", "tile128")


@dataclass(frozen=True)
class Precision:
    """Numeric recipe threaded through the model MLPs (None = full precision).

    ``stale_scale`` is bug 8's injection point; ``use_kernel`` routes the
    quantized matmul through the Pallas kernels."""
    fp8_recipe: Optional[str] = None
    stale_scale: bool = False
    use_kernel: bool = False

    def __post_init__(self):
        if self.fp8_recipe is not None and self.fp8_recipe not in FP8_RECIPES:
            raise ValueError(f"unknown fp8 recipe {self.fp8_recipe!r}")


def _tile_amax(ax):
    """Per-128x128-tile max of ``ax`` -> compact (..., M/tm, N/tn) array."""
    M, N = ax.shape[-2], ax.shape[-1]
    tm, tn = min(TILE, M), min(TILE, N)
    pm, pn = -M % tm, -N % tn
    axp = jnp.pad(ax, [(0, 0)] * (ax.ndim - 2) + [(0, pm), (0, pn)])
    Mp, Np = axp.shape[-2], axp.shape[-1]
    t = axp.reshape(*axp.shape[:-2], Mp // tm, tm, Np // tn, tn)
    return t.max(axis=(-3, -1))                            # (..., mt, nt)


def expand_tile_scale(scale, shape):
    """Broadcast a compact per-tile scale back to the full operand shape.

    Tiles are the fixed ``min(TILE, dim)`` size ``_tile_amax`` grouped by
    (the LAST tile is the ragged one) — recomputing the size from the tile
    count would shift every boundary on non-128-divisible dims."""
    M, N = shape[-2], shape[-1]
    tm, tn = min(TILE, M), min(TILE, N)
    full = jnp.repeat(jnp.repeat(scale, tm, axis=-2), tn, axis=-1)
    return full[..., :M, :N]


def _amax(x, recipe: str):
    ax = jnp.abs(x.astype(jnp.float32))
    if recipe in ("global", "per_tensor"):
        return jnp.max(ax)
    if recipe == "tile128":
        return _tile_amax(ax)
    raise ValueError(recipe)


def quantize_e4m3(x, recipe: str = "global", stale_scale: bool = False):
    """Returns ``(q, scale)`` with ``x ~= q.astype(f32) * scale`` — ``scale``
    is a scalar for global/per_tensor and the COMPACT per-128-tile array for
    tile128 (``expand_tile_scale`` maps it back to the operand shape)."""
    amax = _amax(x, recipe)
    if stale_scale:
        amax = amax * 0.5          # bug 8: scale from a stale (smaller) amax
    scale = jnp.maximum(amax, 1e-12) / E4M3_MAX
    full = expand_tile_scale(scale, x.shape) if recipe == "tile128" else scale
    q = jnp.clip(x.astype(jnp.float32) / full, -E4M3_MAX, E4M3_MAX)
    return q.astype(F8), scale


def _kernel_tileable(x, w) -> bool:
    return (x.ndim == 2 and w.ndim == 2
            and x.shape[0] % TILE == 0 and x.shape[1] % TILE == 0
            and w.shape[1] % TILE == 0)


def fp8_matmul(x, w, recipe: str = "global", stale_scale: bool = False,
               use_kernel: bool = False):
    """x:(...,K) @ w:(K,N) with fp8 operands, fp32 accumulation."""
    qx, sx = quantize_e4m3(x, recipe, stale_scale=stale_scale)
    qw, sw = quantize_e4m3(w, recipe)
    if recipe == "tile128":
        # per-tile scales cannot be folded outside the contraction (they
        # vary along K); the kernel path applies them per 128-block inside
        # the accumulation loop, the XLA path dequantizes per element.
        if use_kernel and _kernel_tileable(qx, qw):
            from repro.kernels import ops as kops
            return kops.fp8_matmul_tile128(qx, sx, qw, sw)
        xd = qx.astype(jnp.float32) * expand_tile_scale(sx, qx.shape)
        wd = qw.astype(jnp.float32) * expand_tile_scale(sw, qw.shape)
        return jnp.matmul(xd, wd)
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.fp8_matmul(qx, qw)
    else:
        out = jnp.matmul(qx.astype(jnp.float32), qw.astype(jnp.float32))
    return out * (sx * sw)


def fp8_linear(p, x, recipe="global", stale_scale=False, use_kernel=False):
    """Straight-through-estimator linear: fp8 forward, bf16/fp32 backward
    (the standard TransformerEngine training arrangement)."""
    w = p["w"]

    @jax.custom_vjp
    def f(x, w):
        y = fp8_matmul(x.reshape(-1, x.shape[-1]), w, recipe,
                       stale_scale=stale_scale, use_kernel=use_kernel)
        return y.reshape(*x.shape[:-1], w.shape[-1]).astype(x.dtype)

    def fwd(x, w):
        return f(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        gx = (g @ w.T.astype(g.dtype)).astype(x.dtype)
        gw = jnp.einsum("...i,...o->io", x.astype(jnp.float32),
                        g.astype(jnp.float32)).astype(w.dtype)
        return gx, gw

    f.defvjp(fwd, bwd)
    y = f(x, w)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Supervisor-facing candidate factories (the CandidateStep contract)
# ---------------------------------------------------------------------------

def _fp8_loss_call(model, precision: Precision):
    def loss_call(p, b, ctx):
        return model.loss(p, b, ctx=ctx, precision=precision)[0]
    return loss_call


def fp8_precision(recipe: str, bugs=frozenset(),
                  use_kernel: bool = False) -> Precision:
    return Precision(fp8_recipe=recipe,
                     stale_scale="fp8_stale_scale" in bugs,
                     use_kernel=use_kernel)


def make_fp8_runner(model, params, recipe: str, opt=None, opt_state=None,
                    bugs=frozenset(), use_kernel: bool = False):
    """Runner(batch, rewrites) -> Trace: the model with FP8 MLP matmuls."""
    from repro.core.collector import make_trace_collector
    collect = make_trace_collector(
        _fp8_loss_call(model, fp8_precision(recipe, bugs, use_kernel)), opt)

    def run(batch, rewrites=None):
        tr, _, _ = collect(params, batch, opt_state, rewrites)
        return tr

    return run


def make_fp8_train_step(model, ref_params, opt, batch, recipe: str,
                        bugs=frozenset(), use_kernel: bool = False):
    """Once-compiled stateful FP8 candidate train step (supervisor contract).

    Returns ``(step, params0, opt_state0)`` with ``step(params, opt_state,
    batch) -> (Trace, new_params, new_opt_state)`` — the low-precision
    recipe trains under supervision of the full-precision reference with
    BF16-epsilon thresholds (paper §6.7)."""
    from repro.core.collector import make_trace_step
    loss_call = _fp8_loss_call(model, fp8_precision(recipe, bugs, use_kernel))
    step = make_trace_step(loss_call, opt, ref_params, batch,
                           name="cand_step")
    params0 = jax.tree.map(jnp.asarray, ref_params)
    return step, params0, opt.init(params0)
