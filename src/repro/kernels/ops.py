"""Jitted public wrappers for the Pallas kernels.

Interpret mode follows the backend: compiled Mosaic on TPU, the Pallas
interpreter on backends with no Mosaic lowering (the CPU test runs).  The
choice is made at call time, not import time — importing this module must
not initialize the JAX backend.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import fp8_matmul as _mm
from repro.kernels import relerr as _re
from repro.kernels import ssm_scan as _ssm


def flash_attention(q, k, v, mode="causal", window=0, bq=512, bk=512):
    return _fa.flash_attention(q, k, v, mode=mode, window=window, bq=bq,
                               bk=bk, interpret=_re.default_interpret())


def gla_scan(q, k, v, log_w, chunk=128, exclusive=False, u=None):
    """Kernel-backed equivalent of models.ssm.lin_attn_chunked (s0=0)."""
    y, s = _ssm.gla_scan(q, k, v, log_w, chunk=chunk, exclusive=exclusive,
                         interpret=_re.default_interpret())
    if u is not None:
        bonus = jnp.einsum("bshk,hk,bshk->bsh", q.astype(jnp.float32),
                           u.astype(jnp.float32), k.astype(jnp.float32))
        y = y + bonus[..., None] * v.astype(jnp.float32)
    return y.astype(v.dtype), s


def fp8_matmul(x, w, bm=256, bn=256, bk=256):
    return _mm.fp8_matmul(x, w, bm=bm, bn=bn, bk=bk,
                          interpret=_re.default_interpret())


def fp8_matmul_tile128(x, sx, w, sw):
    """Per-128x128-tile-scaled fp8 matmul (compact tile scales ride along)."""
    return _mm.fp8_matmul_tile128(x, sx, w, sw,
                                  interpret=_re.default_interpret())


def rel_err(a, b) -> float:
    return _re.rel_err_fused(a, b, interpret=_re.default_interpret())


def packed_sq_norms(a_flat, b_flat, seg_ids, counts, n_segments,
                    block=_re.DEFAULT_BLOCK):
    """Packed segmented (||a-b||^2, ||a||^2) over N pairs in one launch."""
    return _re.packed_sq_norms(a_flat, b_flat, seg_ids, counts,
                               n_segments=n_segments, block=block,
                               interpret=_re.default_interpret())
