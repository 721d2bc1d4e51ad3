"""Expected-FP-round-off-error estimation (paper §5).

The threshold for "is this difference a bug or just floating point?" is
estimated empirically, exactly as §5.2 prescribes: run the reference twice —
once on X and once on X + dX with ||dX|| ~= eps_mch * ||X|| — and record the
induced relative Frobenius error of every traced tensor.  Under the layer
smoothness assumptions (Thm 5.1-5.3) the induced differences track the
accumulated round-off of any *reasonable* FP implementation, so a candidate
whose differences are far above them (paper observes ~100x for real bugs) is
flagged.

For token (integer) inputs the perturbation is applied at the first float
tensor on the differentiation path — the embedding output — via the rewrite
mechanism; for audio/VLM the float frontend features are perturbed directly.
Float-input models additionally take the FUSED estimation path: the base and
perturbed batches are stacked on a leading axis and collected in one vmapped
compiled call (collector.trace_pair_step) instead of two serial jit
round-trips.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.core import canonical as C
from repro.core.collector import Trace
from repro.core.generator import perturb
from repro.core.relerr_engine import rel_err_np

MACHINE_EPS = {
    "float32": 2.0 ** -24,
    "bfloat16": 2.0 ** -8,
    "float16": 2.0 ** -11,
    # fp8 recipes accumulate in >=bf16 (paper §6.7): thresholds are expressed
    # in bf16 epsilons, perturbations injected at bf16 magnitude.
    "float8_e4m3fn": 2.0 ** -8,
}


def rel_err(a: np.ndarray, b: np.ndarray) -> float:
    """Relative Frobenius error ||a-b|| / ||a|| (paper §2.2) for one pair.

    Section-scale comparisons go through relerr_engine.batched_rel_err,
    which picks the device-resident batched path by backend/size; this
    per-pair float64 form stays as the reference semantic.
    """
    return rel_err_np(a, b)


@dataclass
class Thresholds:
    eps: float
    margin: float = 8.0
    floor_mult: float = 4.0
    per_tensor: dict[str, dict[str, float]] = field(default_factory=dict)
    # per kind: {name: estimated FP rel err}

    # Post-step parameters pass through Adam's elementwise m/sqrt(v)
    # normalization, which amplifies *uncorrelated* reduction-order noise
    # more than the correlated perturbation used for estimation; a wider
    # margin absorbs that (bug-induced errors are ~100x above, Fig 8).
    kind_margins = {C.KIND_PARAM_POST: 64.0}

    def threshold(self, kind: str, name: str) -> float:
        est = self.per_tensor.get(kind, {}).get(name, 0.0)
        margin = self.kind_margins.get(kind, self.margin)
        return margin * max(est, self.floor_mult * self.eps)

    def union(self, other: "Thresholds") -> "Thresholds":
        """Elementwise-max merge of two estimates (same eps/margin).

        Periodic re-estimation unions each fresh live-batch estimate into
        the running thresholds: per-tensor floors only ever widen, so a
        batch with unusually low FP noise can never shrink a threshold
        below what an earlier batch already proved reachable."""
        per = {k: dict(v) for k, v in self.per_tensor.items()}
        for kind, named in other.per_tensor.items():
            d = per.setdefault(kind, {})
            for n, e in named.items():
                d[n] = max(d.get(n, 0.0), e)
        return Thresholds(eps=self.eps, margin=self.margin,
                          floor_mult=self.floor_mult, per_tensor=per)


def diff_sections_async(t1: Trace, t2: Trace):
    """Dispatch the per-kind pair reductions of two traces on DEVICE and
    return ``resolve() -> {kind: {name: rel_err}}`` (with ``resolve.ready()``
    probing the device futures).

    This is the single reduction path of threshold estimation: the one-shot
    ``estimate_thresholds`` resolves immediately, the supervised loop's
    periodic re-estimator holds the resolve as an in-flight epoch — both see
    bit-identical estimates because the dispatched computation is the same.
    """
    from repro.core.relerr_engine import _to_rel_err, sq_norms_async
    pend = []
    for kind in (C.KIND_ACT, C.KIND_ACT_GRAD, C.KIND_PARAM_GRAD,
                 C.KIND_MAIN_GRAD, C.KIND_PARAM_POST):
        s1, s2 = t1.section(kind), t2.section(kind)
        names = [n for n in s1 if n in s2]
        dev = sq_norms_async([s1.raw(n) for n in names],
                             [s2.raw(n) for n in names])
        pend.append((kind, names, dev))

    def resolve() -> dict[str, dict[str, float]]:
        out = {}
        for kind, names, dev in pend:
            errs = _to_rel_err(np.asarray(dev, np.float64))
            out[kind] = {n: float(e) for n, e in zip(names, errs)}
        return out

    def ready() -> bool:
        for _, _, dev in pend:
            probe = getattr(dev, "is_ready", None)
            if probe is not None and not probe():
                return False
        return True

    resolve.ready = ready
    return resolve


def _diff_sections(t1: Trace, t2: Trace) -> dict[str, dict[str, float]]:
    return diff_sections_async(t1, t2)()


def _float_keys(batch: dict) -> list[str]:
    return [k for k, v in batch.items()
            if np.issubdtype(np.asarray(v).dtype, np.floating)
            and k != "loss_mask"]


def perturbed_batch_or_rewrites(batch: dict, base_trace: Trace,
                                eps: float, seed: int = 0):
    """Returns (batch', rewrites').  Float model inputs are perturbed in the
    batch; token-only models are perturbed at the embedding output."""
    float_keys = _float_keys(batch)
    if float_keys:
        b2 = dict(batch)
        for i, k in enumerate(float_keys):
            b2[k] = perturb(np.asarray(batch[k]), eps, seed=seed + i)
        return b2, None
    emb = "embedding/output"
    assert emb in base_trace.activations, (
        "no float inputs and no embedding/output tap to perturb")
    rew = {emb: perturb(base_trace.activations[emb], eps, seed=seed)}
    return batch, rew


def estimate_thresholds(run_trace, batch: dict, eps: float,
                        margin: float = 8.0, seed: int = 0) -> tuple[
                            Thresholds, Trace]:
    """``run_trace(batch, rewrites) -> Trace`` runs the REFERENCE.

    Returns (thresholds, base_reference_trace) — the base trace is reused as
    the reference side of the differential test, so threshold estimation
    costs exactly one extra iteration (paper §3 step 1).

    If the runner exposes ``.pair`` (collector.trace_pair_step underneath)
    and the batch has float inputs, base and perturbed runs are stacked and
    collected in one compiled call; otherwise the two runs stay serial (the
    token-input perturbation needs the base trace's embedding output before
    the perturbed run can start).
    """
    t1 = t2 = None
    pair = getattr(run_trace, "pair", None)
    if pair is not None and _float_keys(batch):
        b2, _ = perturbed_batch_or_rewrites(batch, None, eps, seed)
        stacked = {k: np.stack([np.asarray(batch[k]), np.asarray(b2[k])])
                   for k in batch}
        try:
            t1, t2 = pair(stacked)
        except NotImplementedError as e:
            # a primitive with no batching rule: the model cannot be
            # vmapped, so the two runs go serially.  Anything else (out of
            # memory, a compiler refusal) is a real failure and propagates
            import warnings
            warnings.warn(
                "fused threshold estimation failed "
                f"({type(e).__name__}: {e}); falling back to two serial "
                "reference runs", RuntimeWarning)
            t1 = t2 = None
    if t1 is None:
        t1 = run_trace(batch, None)
        b2, rew = perturbed_batch_or_rewrites(batch, t1, eps, seed)
        t2 = run_trace(b2, rew)
    thr = Thresholds(eps=eps, margin=margin, per_tensor=_diff_sections(t1, t2))
    return thr, t1


# ---------------------------------------------------------------------------
# Once-compiled fused pair estimator (periodic re-estimation, paper §5 live)
# ---------------------------------------------------------------------------

_EMB_TAP = "embedding/output"


def make_pair_estimator(loss_call, opt, params, batch, eps: float,
                        margin: float = 8.0, seed: int = 0, device=None):
    """Build ``estimate(params, opt_state, batch) -> Thresholds`` compiled
    exactly once — the supervised loop's periodic threshold RE-estimation.

    ``estimate.submit(params, opt_state, batch, step)`` is the ASYNC form:
    it dispatches the pair collection and the per-kind reductions on device
    (under ``device`` when given — the supervisor's reference device set)
    and returns ``resolve() -> Thresholds`` with ``resolve.ready()``; the
    synchronous ``estimate`` is exactly ``submit(...)()``, so overlapped
    and lockstep re-estimation produce bit-identical thresholds.

    The pair collection itself is ``collector.make_pair_collector`` — the
    same build-once vmapped base+perturbed run ``trace_fn_pair`` (and with
    it the one-shot fused estimation path) uses, so the two paths cannot
    drift.  Float model inputs are perturbed per-row in the stacked batch;
    token-only models fold the embedding-output perturbation INTO the
    stacked run via a per-row callable rewrite
    ``x + flag * eps * ||x|| * d/||d||`` (flag 0 on the base row) — the
    fused path the serial estimator cannot take because the one-shot
    rewrite needs the base trace first.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.collector import make_pair_collector

    batch_t = {k: jnp.asarray(v) for k, v in batch.items()}
    float_keys = _float_keys(batch_t)
    token_mode = not float_keys
    base_key = jax.random.PRNGKey(seed ^ 0x5EED)

    row_rewrite = None
    if token_mode:
        def row_rewrite(flag, step_k):
            def perturb_tap(x):
                # directional eps-noise gated by the row flag; matches
                # generator.perturb semantics (||dX|| = eps * ||X||).
                # The direction varies per re-estimation (step folded
                # into the key, like the float path's per-step seed) so
                # the union explores new directions each epoch.
                d = jax.random.normal(jax.random.fold_in(base_key, step_k),
                                      x.shape, jnp.float32)
                nx = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                nd = jnp.maximum(jnp.sqrt(jnp.sum(jnp.square(d))), 1e-30)
                return x.astype(jnp.float32) + flag * (eps * nx / nd) * d
            return {_EMB_TAP: perturb_tap}

    collect = make_pair_collector(loss_call, opt, params, batch_t,
                                  row_rewrite=row_rewrite, device=device)
    if token_mode and _EMB_TAP not in collect.shapes:
        raise ValueError("no float inputs and no embedding/output tap — "
                         "cannot build a fused pair estimator")

    def submit(p, st, live_batch, step: int = 0):
        if token_mode:
            b2 = {k: jnp.stack([jnp.asarray(v)] * 2)
                  for k, v in live_batch.items()}
        else:
            b2 = {}
            for i, k in enumerate(live_batch):
                base = np.asarray(live_batch[k])
                pert = (perturb(base, eps, seed=seed + step * 131 + i)
                        if k in float_keys else base)
                b2[k] = jnp.stack([jnp.asarray(base), jnp.asarray(pert)])
        t0, t1 = collect(p, st, b2, step=step)
        pend = diff_sections_async(t0, t1)

        def resolve() -> Thresholds:
            return Thresholds(eps=eps, margin=margin, per_tensor=pend())

        resolve.ready = pend.ready
        return resolve

    def estimate(p, st, live_batch, step: int = 0) -> Thresholds:
        return submit(p, st, live_batch, step=step)()

    estimate.submit = submit
    return estimate
