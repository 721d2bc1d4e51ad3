"""Pallas fused relative-Frobenius-error reductions — the checker's hot loop.

TTrace's equivalence checker computes ||A - B||_F / ||A||_F over every traced
tensor; the paper implements this in multithreaded C++ to dodge the GIL.  The
TPU-idiomatic equivalent is a *packed segmented* reduction: all N tensor
pairs of a trace section are concatenated (block-aligned) into two flat
buffers, and ONE grid launch walks both buffers block-by-block, accumulating
``(||a-b||^2, ||a||^2)`` into the row of an (N, 2) output selected by the
block's scalar-prefetched segment id.  Neither the difference tensor nor a
second read of A is ever materialized in HBM, and the host pulls back only
N x 2 floats.

Layout contract (produced by repro.core.relerr_engine.pack_sections):

* each pair's elements are flattened and placed at a ``block``-aligned
  offset; the tail of its last block is zero-filled,
* ``seg_ids[i]`` is the pair index owning block i (blocks never straddle
  pairs),
* ``counts[i]`` is the number of valid elements in block i (== block except
  for each pair's ragged last block); the kernel masks the zero-fill, so
  NaN/Inf garbage in padding can never leak into a verdict.

``sq_norms`` (single pair) is a thin wrapper over the packed kernel with
N == 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Packed blocks are (BLOCK // LANES, LANES) f32 tiles; 1024 = 8 x 128, the
# native TPU vreg tile, and small enough that per-pair alignment padding is
# negligible for trace-scale tensors.
LANES = 128
DEFAULT_BLOCK = 1024
# The per-block segment ids and counts are scalar-prefetched into SMEM,
# which holds 1 MiB on v5e: a float32 parameter section of TinyLlama's
# 2-layer cut (154M elements, 150k blocks) overflows it.  One launch covers
# at most this many blocks (512 KiB of metadata); a longer section takes
# several launches over the same buffers, whose (N, 2) partial sums add.
MAX_LAUNCH_BLOCKS = 1 << 16


def default_interpret() -> bool:
    """Interpret mode is for backends with no Mosaic lowering (CPU here);
    on TPU the same kernels compile."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# packed segmented kernel
# ---------------------------------------------------------------------------

def _packed_relerr_kernel(seg_ref, cnt_ref, a_ref, b_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    rows, lanes = a.shape
    lin = (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    # select, not multiply-by-0/1: 0 * NaN is NaN, and the padding contract
    # must hold even over garbage tails (e.g. reused buffers)
    valid = lin < cnt_ref[i]
    d = jnp.where(valid, a - b, 0.0)
    a = jnp.where(valid, a, 0.0)
    seg = seg_ref[i]
    upd = jnp.stack([jnp.sum(d * d), jnp.sum(a * a)]).reshape(1, 2)
    out_ref[pl.ds(seg, 1), :] += upd


@functools.partial(jax.jit,
                   static_argnames=("n_segments", "block", "interpret"))
def packed_sq_norms(a_flat, b_flat, seg_ids, counts, n_segments: int,
                    block: int = DEFAULT_BLOCK,
                    interpret: bool | None = None):
    """Grid launches over the packed section -> (n_segments, 2) f32 of
    ``(||a-b||^2, ||a||^2)`` per pair: one launch per
    ``MAX_LAUNCH_BLOCKS`` blocks.

    ``a_flat``/``b_flat``: packed flat buffers, length divisible by
    ``block``; ``seg_ids``/``counts``: int32 per-block metadata (see module
    docstring).  ``interpret=None`` auto-selects from the backend.
    """
    if interpret is None:
        interpret = default_interpret()
    assert block % LANES == 0, f"block {block} must be a multiple of {LANES}"
    rows = block // LANES
    nb = a_flat.shape[0] // block
    a2 = a_flat.reshape(nb * rows, LANES)
    b2 = b_flat.reshape(nb * rows, LANES)
    out = None
    for start in range(0, nb, MAX_LAUNCH_BLOCKS):
        n = min(MAX_LAUNCH_BLOCKS, nb - start)

        def in_block(i, *_, start=start):
            return (start + i, 0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[pl.BlockSpec((rows, LANES), in_block),
                      pl.BlockSpec((rows, LANES), in_block)],
            out_specs=pl.BlockSpec((n_segments, 2), lambda i, *_: (0, 0)),
        )
        part = pl.pallas_call(
            _packed_relerr_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_segments, 2), jnp.float32),
            # every grid step accumulates into the one resident output
            # block, so the grid axis must run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="relerr_kernel",
        )(seg_ids[start:start + n], counts[start:start + n], a2, b2)
        out = part if out is None else out + part
    return out


def packed_sq_norms_xla(a_flat, b_flat, seg_ids, n_segments: int,
                        block: int = DEFAULT_BLOCK):
    """Pure-XLA executor of the same packed layout (the kernel's oracle and
    the compiled fallback on backends without Mosaic).  Padding is
    zero-filled by the packing contract, so no mask is needed: zeros
    contribute nothing to either sum."""
    a = a_flat.astype(jnp.float32)
    b = b_flat.astype(jnp.float32)
    nb = a.shape[0] // block
    d = a - b
    dd = jnp.sum((d * d).reshape(nb, block), axis=1)
    aa = jnp.sum((a * a).reshape(nb, block), axis=1)
    return jax.ops.segment_sum(jnp.stack([dd, aa], axis=1), seg_ids,
                               num_segments=n_segments)


# ---------------------------------------------------------------------------
# single-pair wrappers (legacy surface)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sq_norms(a, b, block: int = 65536,
             interpret: bool | None = None):
    """Returns (||a-b||^2, ||a||^2) for ONE pair — thin wrapper over the
    packed segmented kernel with a single segment.

    The default block is much larger than the packed layout's
    DEFAULT_BLOCK: with N == 1 there is no alignment waste, and fewer grid
    steps means less per-step overhead (especially in interpret mode)."""
    af = jnp.asarray(a).reshape(-1).astype(jnp.float32)
    bf = jnp.asarray(b).reshape(-1).astype(jnp.float32)
    n = af.shape[0]
    pad = -n % block if n else block
    if pad:
        af = jnp.pad(af, (0, pad))
        bf = jnp.pad(bf, (0, pad))
    nb = af.shape[0] // block
    seg_ids = jnp.zeros((nb,), jnp.int32)
    counts = jnp.clip(n - jnp.arange(nb, dtype=jnp.int32) * block, 0, block)
    out = packed_sq_norms(af, bf, seg_ids, counts, n_segments=1,
                          block=block, interpret=interpret)
    return out[0, 0], out[0, 1]


def rel_err_fused(a, b, interpret: bool | None = None) -> float:
    d2, a2 = sq_norms(a, b, interpret=interpret)
    d2, a2 = float(d2), float(a2)
    return (d2 ** 0.5) / (a2 ** 0.5) if a2 > 0 else d2 ** 0.5
