"""Batched, device-resident rel-err engine — the checker's comparison core.

``compare_traces`` and ``thresholds._diff_sections`` both reduce to the same
question: for N tensor pairs of one trace section, what are the N relative
Frobenius errors?  This module answers it in (at most) one device dispatch
per section instead of N host-side float64 loops:

* **TPU**: the pairs are packed into block-aligned flat buffers on
  device and handed to the packed segmented Pallas kernel
  (``repro.kernels.relerr.packed_sq_norms``) — one program with one
  launch per group of at most ``PACK_GROUP_ELEMS`` elements, N x 2
  scalars transferred back.
* **CPU**: device buffers ARE host memory, so the fastest executor is f32
  BLAS over zero-copy numpy views — in-place subtract into a reused scratch
  plus two sdot reductions per pair, no float64 temporaries, no
  allocations.  (Packing through host memory or XLA:CPU's reduce codegen
  both lose to this by 3-10x at trace scale.)
* **other accelerators (no Mosaic)**: the same fused
  subtract-square-reduce per pair inside ONE jitted call — a single
  dispatch, leaves stay on device, no difference tensor materialized.
* **below a per-backend size cutoff**: a plain per-pair float64 numpy loop
  — for tiny sections the compile + dispatch overhead of any batched path
  dwarfs the arithmetic, and float64 is the reference semantic.

The selection is automatic from ``jax.default_backend()`` (this replaced
the old ``REPRO_FUSED_RELERR_MIN_ELEMS`` env var); ``mode=`` forces a
specific path for tests and benchmarks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import relerr as K

# Below this many total section elements the float64 numpy loop wins
# (TPU/GPU: keep even small sections on device — each host transfer costs
# more than a tiny kernel; the CPU crossover uses the per-pair mean below).
MIN_BATCHED_ELEMS = {"tpu": 1 << 12, "gpu": 1 << 14}

# CPU crossover refinement: both executors are per-pair host loops, so the
# crossover tracks the MEAN elements per pair, not the section total — the
# loop pays float64 temporaries per element (2x bandwidth) but less per-pair
# fixed cost than the BLAS scratch path.  Measured on the container's 2-core
# host (see checker_bench's auto rows): loop wins below ~4k elements/pair at
# every section width from 20 to 200 tensors, BLAS above.  The old
# total-elements cutoff misclassified exactly the bench's 50x128k section
# (721us batched vs 535us loop).
MIN_BATCHED_MEAN_ELEMS_CPU = 1 << 12


def _raw(section, name):
    """Stored leaf without forcing a host copy (Section.raw or dict item)."""
    getter = getattr(section, "raw", None)
    return getter(name) if getter is not None else section[name]


def rel_err_np(a, b) -> float:
    """Per-pair float64 reference: ||a-b|| / ||a|| (paper §2.2)."""
    a64 = np.asarray(a, np.float64)
    b64 = np.asarray(b, np.float64)
    na = np.linalg.norm(a64)
    d = np.linalg.norm(a64 - b64)
    return float(d / na) if na > 0 else float(d)


# ---------------------------------------------------------------------------
# device paths
# ---------------------------------------------------------------------------

@jax.jit
def relerr_fused(leaves_a, leaves_b):
    """One compiled call over all pairs: [(||a-b||^2, ||a||^2)] -> (N, 2).

    Retraces per section signature (pytree of shapes/dtypes); the jit cache
    makes repeated checks of same-shaped traces free.
    """
    dd, aa = [], []
    for a, b in zip(leaves_a, leaves_b):
        a = a.reshape(-1).astype(jnp.float32)
        b = b.reshape(-1).astype(jnp.float32)
        d = a - b
        dd.append(jnp.vdot(d, d))
        aa.append(jnp.vdot(a, a))
    return jnp.stack([jnp.stack(dd), jnp.stack(aa)], axis=1)


def pack_device(leaves_a, leaves_b, block: int = K.DEFAULT_BLOCK):
    """Pack pairs into the kernel's flat block-aligned layout on device.

    Returns (a_flat, b_flat, seg_ids, counts); see kernels.relerr for the
    layout contract.  Metadata is computed host-side from static shapes —
    no leaf is transferred.
    """
    sizes = [int(np.prod(x.shape)) for x in leaves_a]
    nblocks = [max(1, -(-s // block)) for s in sizes]

    def pad(x):
        f = jnp.ravel(x).astype(jnp.float32)
        p = -f.shape[0] % block if f.shape[0] else block
        return jnp.pad(f, (0, p)) if p else f

    a_flat = jnp.concatenate([pad(x) for x in leaves_a])
    b_flat = jnp.concatenate([pad(x) for x in leaves_b])
    seg_ids = np.repeat(np.arange(len(sizes), dtype=np.int32), nblocks)
    counts = np.concatenate([
        np.clip(s - np.arange(nb, dtype=np.int64) * block, 0, block)
        for s, nb in zip(sizes, nblocks)]).astype(np.int32)
    return a_flat, b_flat, jnp.asarray(seg_ids), jnp.asarray(counts)


# Largest packed copy (elements per side) one kernel launch reads.  A
# full-width trace is GBs, and packing it whole doubled it in HBM (an
# out-of-memory at TinyLlama's widths on one v5e chip); packed in groups
# inside one program, the copies of one group die before the next is
# made.  A leaf larger than this is packed alone, and a block-aligned one
# then reaches the kernel without any copy.
PACK_GROUP_ELEMS = 1 << 24


def _pack_groups(sizes) -> list[tuple[int, int]]:
    groups, lo, acc = [], 0, 0
    for i, n in enumerate(sizes):
        if i > lo and acc + n > PACK_GROUP_ELEMS:
            groups.append((lo, i))
            lo, acc = i, 0
        acc += n
    groups.append((lo, len(sizes)))
    return groups


@jax.jit
def relerr_packed(leaves_a, leaves_b):
    """(N, 2) ``(||a-b||^2, ||a||^2)`` on the packed kernel: one program,
    one kernel launch per group of pairs."""
    from repro.kernels import ops
    outs = []
    for lo, hi in _pack_groups([x.size for x in leaves_a]):
        a_flat, b_flat, seg_ids, counts = pack_device(leaves_a[lo:hi],
                                                      leaves_b[lo:hi])
        outs.append(ops.packed_sq_norms(a_flat, b_flat, seg_ids, counts,
                                        n_segments=hi - lo))
    return jnp.concatenate(outs)


def _packed_path(leaves_a, leaves_b) -> np.ndarray:
    out = relerr_packed([jnp.asarray(x) for x in leaves_a],
                        [jnp.asarray(x) for x in leaves_b])
    return np.asarray(out, np.float64)


def _fused_path(leaves_a, leaves_b) -> np.ndarray:
    out = relerr_fused([jnp.asarray(x) for x in leaves_a],
                       [jnp.asarray(x) for x in leaves_b])
    return np.asarray(out, np.float64)


def _blas_path(leaves_a, leaves_b) -> np.ndarray:
    """CPU fast path: f32 BLAS over zero-copy views of the leaves."""
    def as_f32(x):
        v = np.asarray(x)                 # zero-copy for CPU jax f32 arrays
        if v.dtype != np.float32:
            v = np.asarray(v, np.float32)
        return v.reshape(-1)

    out = np.empty((len(leaves_a), 2), np.float64)
    scratch = np.empty(max(int(np.prod(x.shape)) for x in leaves_a),
                       np.float32)
    for i, (a, b) in enumerate(zip(leaves_a, leaves_b)):
        an, bn = as_f32(a), as_f32(b)
        d = scratch[:an.size]
        np.subtract(an, bn, out=d)
        out[i, 0] = np.dot(d, d)
        out[i, 1] = np.dot(an, an)
    return out


# ---------------------------------------------------------------------------
# engine entry points
# ---------------------------------------------------------------------------

def section_sq_norms(leaves_a, leaves_b, mode: str | None = None
                     ) -> np.ndarray:
    """(N, 2) float64 of ``(||a-b||^2, ||a||^2)`` per pair.

    ``mode``: None (auto by backend/size), "loop", "blas", "fused", or
    "packed".
    """
    if not leaves_a:
        return np.zeros((0, 2), np.float64)
    if mode is None:
        backend = jax.default_backend()
        # .size, not np.prod(shape): the selection runs per check and a
        # np.prod call per leaf costs more than the small-section reduction
        total = sum(int(x.size) for x in leaves_a)
        if backend == "cpu":
            # host executors: the crossover is per-pair, not per-section
            mode = ("loop" if total // len(leaves_a)
                    < MIN_BATCHED_MEAN_ELEMS_CPU else "blas")
        elif total < MIN_BATCHED_ELEMS.get(backend, 1 << 19):
            mode = "loop"
        elif backend == "tpu":
            mode = "packed"
        else:
            mode = "fused"
    if mode == "loop":
        out = np.empty((len(leaves_a), 2), np.float64)
        for i, (a, b) in enumerate(zip(leaves_a, leaves_b)):
            a64 = np.asarray(a, np.float64).reshape(-1)
            b64 = np.asarray(b, np.float64).reshape(-1)
            d = a64 - b64
            out[i, 0] = np.dot(d, d)
            out[i, 1] = np.dot(a64, a64)
        return out
    if mode == "blas":
        return _blas_path(leaves_a, leaves_b)
    if mode == "fused":
        return _fused_path(leaves_a, leaves_b)
    if mode == "packed":
        return _packed_path(leaves_a, leaves_b)
    raise ValueError(f"unknown rel-err engine mode {mode!r}")


def sq_norms_async(leaves_a, leaves_b):
    """Dispatch the per-pair ``(||a-b||^2, ||a||^2)`` reduction and return
    the DEVICE ``(N, 2)`` array **without synchronizing**.

    This is the async-checking entry point: the caller keeps the returned
    ``jax.Array`` as a future (JAX dispatch is asynchronous on every
    backend) and materializes it later with ``np.asarray`` — training steps
    dispatched in between overlap with the reduction.  On TPU the packed
    segmented Pallas kernel runs; elsewhere the fused one-dispatch XLA
    reduction.  (The CPU BLAS executor is intentionally NOT used here: it
    computes on the caller's thread, which is exactly the synchronization
    async checking exists to avoid.)
    """
    if not leaves_a:
        return jnp.zeros((0, 2), jnp.float32)
    if jax.default_backend() == "tpu":
        return relerr_packed([jnp.asarray(x) for x in leaves_a],
                             [jnp.asarray(x) for x in leaves_b])
    return relerr_fused([jnp.asarray(x) for x in leaves_a],
                        [jnp.asarray(x) for x in leaves_b])


def _to_rel_err(sq: np.ndarray) -> np.ndarray:
    d = np.sqrt(sq[:, 0])
    na = np.sqrt(sq[:, 1])
    return np.where(na > 0, d / np.maximum(na, 1e-300), d)


def batched_rel_err(section_a, section_b, names=None,
                    mode: str | None = None) -> dict[str, float]:
    """Relative Frobenius errors for every pair in a trace section.

    ``section_a/b``: collector.Section or plain dict; leaves stay device
    resident on the batched paths — only N x 2 scalars reach the host.
    ``names`` defaults to the keys of ``section_a`` present in ``section_b``
    (in ``section_a`` order); pairs must be same-shaped.
    """
    if names is None:
        names = [k for k in section_a if k in section_b]
    leaves_a = [_raw(section_a, n) for n in names]
    leaves_b = [_raw(section_b, n) for n in names]
    errs = _to_rel_err(section_sq_norms(leaves_a, leaves_b, mode=mode))
    return {n: float(e) for n, e in zip(names, errs)}
