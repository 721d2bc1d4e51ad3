"""Packed segmented rel-err kernel + batched checking engine + lazy Trace."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # no PyPI route in CI image
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import canonical as C
from repro.core.checker import CheckRecord, compare_traces
from repro.core.collector import Section, Trace, trace_pair_step, \
    trace_train_step
from repro.core.relerr_engine import (batched_rel_err, pack_device,
                                      rel_err_np, section_sq_norms)
from repro.core.thresholds import Thresholds
from repro.kernels import relerr as relerr_kernels
from repro.kernels.relerr import DEFAULT_BLOCK, packed_sq_norms, \
    packed_sq_norms_xla, sq_norms

BLOCK = DEFAULT_BLOCK


def _pairs(sizes, seed=0, dtype=np.float32, rel=1e-3):
    rng = np.random.default_rng(seed)
    out = []
    for n in sizes:
        a = (rng.standard_normal(n) * rng.uniform(0.01, 10)).astype(dtype)
        b = (a.astype(np.float32)
             + rel * rng.standard_normal(n).astype(np.float32)).astype(dtype)
        out.append((a, b))
    return out


def _ref_sq(pairs):
    out = np.empty((len(pairs), 2), np.float64)
    for i, (a, b) in enumerate(pairs):
        a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
        out[i] = [np.sum((a64 - b64) ** 2), np.sum(a64 ** 2)]
    return out


# ---------------------------------------------------------------------------
# packed segmented kernel
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 1000),
       dtype=st.sampled_from([np.float32, "bfloat16"]))
@settings(max_examples=8, deadline=None)
def test_packed_kernel_ragged_sizes_property(seed, dtype):
    if dtype == "bfloat16":
        dtype = jnp.bfloat16
    sizes = [1, BLOCK - 1, BLOCK, BLOCK + 1, 3 * BLOCK + 17, 5]
    pairs = [(jnp.asarray(a, dtype), jnp.asarray(b, dtype))
             for a, b in _pairs(sizes, seed=seed)]
    af, bf, seg, cnt = pack_device([a for a, _ in pairs],
                                   [b for _, b in pairs])
    got = np.asarray(packed_sq_norms(af, bf, seg, cnt,
                                     n_segments=len(pairs)), np.float64)
    want = _ref_sq(pairs)
    tol = 1e-4 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=1e-12)


@pytest.mark.parametrize("launch_blocks", [None, 2])
def test_packed_kernel_matches_xla_oracle(monkeypatch, launch_blocks):
    """Also when the section spans several launches (the SMEM bound on
    the scalar-prefetched metadata): pairs then straddle launches."""
    if launch_blocks is not None:
        monkeypatch.setattr(relerr_kernels, "MAX_LAUNCH_BLOCKS",
                            launch_blocks)
    packed_sq_norms.clear_cache()
    sizes = [7, BLOCK, 2 * BLOCK + 3]
    pairs = _pairs(sizes, seed=3)
    af, bf, seg, cnt = pack_device([jnp.asarray(a) for a, _ in pairs],
                                   [jnp.asarray(b) for _, b in pairs])
    kern = np.asarray(packed_sq_norms(af, bf, seg, cnt, n_segments=3))
    packed_sq_norms.clear_cache()
    orac = np.asarray(packed_sq_norms_xla(af, bf, seg, n_segments=3))
    np.testing.assert_allclose(kern, orac, rtol=1e-6)


def test_packed_kernel_masks_padding_garbage():
    """NaN in the padding tail must not leak into any pair's sums."""
    n = BLOCK + 5
    a = np.ones(n, np.float32)
    b = np.full(n, 2.0, np.float32)
    af = np.full(2 * BLOCK, np.nan, np.float32)
    bf = np.full(2 * BLOCK, np.nan, np.float32)
    af[:n], bf[:n] = a, b
    seg = jnp.asarray([0, 0], jnp.int32)
    cnt = jnp.asarray([BLOCK, n - BLOCK], jnp.int32)
    out = np.asarray(packed_sq_norms(jnp.asarray(af), jnp.asarray(bf),
                                     seg, cnt, n_segments=1))
    np.testing.assert_allclose(out[0], [n, n], rtol=1e-6)


def test_packed_kernel_zero_reference_and_empty():
    z = jnp.zeros(16, jnp.float32)
    o = jnp.ones(16, jnp.float32)
    e = jnp.zeros(0, jnp.float32)
    af, bf, seg, cnt = pack_device([z, e], [o, e])
    out = np.asarray(packed_sq_norms(af, bf, seg, cnt, n_segments=2))
    np.testing.assert_allclose(out[0], [16.0, 0.0], rtol=1e-6)
    np.testing.assert_allclose(out[1], [0.0, 0.0])


@pytest.mark.parametrize("group_elems", [None, BLOCK])
def test_packed_engine_groups_match_loop(monkeypatch, group_elems):
    """The engine's packed path packs pairs in bounded groups (one kernel
    launch each) inside one program; any grouping gives the loop's sums."""
    from repro.core import relerr_engine
    if group_elems is not None:
        monkeypatch.setattr(relerr_engine, "PACK_GROUP_ELEMS", group_elems)
    relerr_engine.relerr_packed.clear_cache()
    pairs = _pairs([5, BLOCK, 3 * BLOCK + 1, 17, 2 * BLOCK], seed=11)
    la = [jnp.asarray(a) for a, _ in pairs]
    lb = [jnp.asarray(b) for _, b in pairs]
    got = section_sq_norms(la, lb, mode="packed")
    relerr_engine.relerr_packed.clear_cache()
    np.testing.assert_allclose(got, section_sq_norms(la, lb, mode="loop"),
                               rtol=1e-5)


def test_single_pair_sq_norms_wrapper():
    a, b = _pairs([4 * BLOCK + 11], seed=7)[0]
    d2, a2 = sq_norms(a, b)
    want = _ref_sq([(a, b)])[0]
    np.testing.assert_allclose([float(d2), float(a2)], want, rtol=1e-4)


# ---------------------------------------------------------------------------
# engine: mode agreement + section semantics
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 10_000), rel=st.floats(1e-7, 1e-1))
@settings(max_examples=8, deadline=None)
def test_engine_modes_agree_property(seed, rel):
    sizes = [1, 3, BLOCK - 1, BLOCK + 1, 2000]
    pairs = _pairs(sizes, seed=seed, rel=rel)
    sec_a = {f"t{i}": a for i, (a, _) in enumerate(pairs)}
    sec_b = {f"t{i}": b for i, (_, b) in enumerate(pairs)}
    want = {k: rel_err_np(sec_a[k], sec_b[k]) for k in sec_a}
    for mode in ("loop", "blas", "fused", "packed"):
        got = batched_rel_err(sec_a, sec_b, mode=mode)
        for k in want:
            assert got[k] == pytest.approx(want[k], rel=1e-3, abs=1e-10), \
                (mode, k)


def test_engine_auto_mode_runs():
    pairs = _pairs([64, 128], seed=1)
    sec_a = {f"t{i}": a for i, (a, _) in enumerate(pairs)}
    sec_b = {f"t{i}": b for i, (_, b) in enumerate(pairs)}
    got = batched_rel_err(sec_a, sec_b)            # backend/size auto-select
    want = {k: rel_err_np(sec_a[k], sec_b[k]) for k in sec_a}
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6)


def test_engine_empty_section():
    assert batched_rel_err({}, {}) == {}
    assert section_sq_norms([], []).shape == (0, 2)


# ---------------------------------------------------------------------------
# compare_traces regression: identical Report records vs the old loop
# ---------------------------------------------------------------------------

def _compare_traces_legacy(ref, cand, thr, kinds):
    """The pre-refactor per-tensor float64 loop, verbatim semantics."""
    records, missing = [], []
    for kind in kinds:
        rs, cs = ref.section(kind), cand.section(kind)
        for name, a in rs.items():
            if name not in cs:
                missing.append(f"{kind}:{name} missing from candidate")
                continue
            b = cs[name]
            if a.shape != b.shape:
                records.append(CheckRecord(
                    kind, name, float("inf"), 0.0, True,
                    note=f"shape {b.shape} != ref {a.shape}"))
                continue
            e = rel_err_np(a, b)
            t = thr.threshold(kind, name)
            records.append(CheckRecord(kind, name, e, t, e > t))
    return records, missing


def _build_regression_traces():
    rng = np.random.default_rng(5)
    ref, cand = Trace(), Trace()
    acts_r, acts_c = {}, {}
    for i in range(40):
        n = int(rng.integers(1, 3000))
        a = rng.standard_normal(n).astype(np.float32)
        scale = 1e-7 if i % 3 else 1e-2          # mixed pass/fail
        acts_r[f"layers.{i}.mlp/output"] = a
        acts_c[f"layers.{i}.mlp/output"] = \
            a + scale * rng.standard_normal(n).astype(np.float32)
    acts_c["layers.0.mlp/output"] = np.zeros((2, 2), np.float32)  # shape mism
    acts_r["only_ref/output"] = np.ones(4, np.float32)            # missing
    ref.activations, cand.activations = acts_r, acts_c
    ref.meta["fwd_order"] = list(acts_r)
    return ref, cand, Thresholds(eps=2.0 ** -24)


def _assert_matches_legacy(ref, cand, thr, rel_err_tol):
    rep = compare_traces(ref, cand, thr, kinds=(C.KIND_ACT,))
    legacy_records, legacy_missing = _compare_traces_legacy(
        ref, cand, thr, kinds=(C.KIND_ACT,))

    assert rep.missing == legacy_missing
    assert len(rep.records) == len(legacy_records)
    for got, want in zip(rep.records, legacy_records):
        assert (got.kind, got.name, got.note) == \
            (want.kind, want.name, want.note)
        assert got.threshold == want.threshold
        assert got.flagged == want.flagged       # bit-identical flag decision
        if np.isfinite(want.rel_err):
            assert got.rel_err == pytest.approx(want.rel_err,
                                                rel=rel_err_tol, abs=1e-12)


def test_compare_traces_matches_legacy_loop():
    ref, cand, thr = _build_regression_traces()
    # sections are below the engine cutoff -> auto mode is the float64 loop
    _assert_matches_legacy(ref, cand, thr, rel_err_tol=1e-6)


def test_compare_traces_matches_legacy_on_batched_path(monkeypatch):
    """Flag parity must hold on the batched executor production traces
    actually take (above-cutoff sections), not just the float64 loop."""
    from repro.core import relerr_engine
    monkeypatch.setattr(relerr_engine, "MIN_BATCHED_ELEMS",
                        {k: 0 for k in relerr_engine.MIN_BATCHED_ELEMS})
    ref, cand, thr = _build_regression_traces()
    _assert_matches_legacy(ref, cand, thr, rel_err_tol=1e-4)


# ---------------------------------------------------------------------------
# lazy Trace contract
# ---------------------------------------------------------------------------

def test_section_lazy_host_boundary():
    s = Section({"x": jnp.arange(6.0), "y": np.ones(3)})
    assert isinstance(s.raw("x"), jax.Array)     # no transfer on raw access
    assert s.shape_of("x") == (6,)
    assert not s._host                            # nothing materialized yet
    h = s["x"]
    assert isinstance(h, np.ndarray)
    assert s["x"] is h                            # cached
    s["x"] = jnp.zeros(2)                         # write invalidates cache
    np.testing.assert_allclose(s["x"], np.zeros(2))
    assert set(s.host()) == {"x", "y"}


def test_trace_adopts_plain_dicts():
    t = Trace()
    t.activations = {"a/output": np.ones(2, np.float32)}
    assert isinstance(t.activations, Section)
    t2 = Trace(activations={"b/output": jnp.ones(2)})
    assert isinstance(t2.activations, Section)
    assert isinstance(t2.host().activations["b/output"], np.ndarray)


def test_compare_traces_does_not_materialize_device_sections():
    """A full check of matching device-resident sections must not populate
    any host cache — only the N x 2 reduction scalars come back."""
    leaves = {f"t{i}/output": jnp.asarray(
        np.random.default_rng(i).standard_normal(500).astype(np.float32))
        for i in range(8)}
    ref, cand = Trace(), Trace()
    ref.activations = dict(leaves)
    cand.activations = dict(leaves)
    ref.meta["fwd_order"] = list(leaves)
    rep = compare_traces(ref, cand, Thresholds(eps=2.0 ** -24),
                         kinds=(C.KIND_ACT,))
    assert rep.passed
    assert not ref.activations._host and not cand.activations._host


def test_collector_sections_stay_device_resident():
    cfg = dataclasses.replace(
        __import__("repro.configs.base", fromlist=["get_config"])
        .get_config("gpt-paper").reduced(), n_layers=1, vocab=128)
    from repro.models.model import Model
    from repro.data.synthetic import make_batch
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    tr, _, _ = trace_train_step(m, params, make_batch(cfg, 2, 8))
    for name in tr.activations:
        assert isinstance(tr.activations.raw(name), jax.Array)
    assert not tr.activations._host


# ---------------------------------------------------------------------------
# fused pair collection == two serial steps
# ---------------------------------------------------------------------------

def test_trace_pair_step_matches_serial():
    cfg = dataclasses.replace(
        __import__("repro.configs.base", fromlist=["get_config"])
        .get_config("gpt-paper").reduced(), n_layers=1, vocab=128)
    from repro.models.model import Model
    from repro.data.synthetic import make_batch
    from repro.optim.adamw import AdamW
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-3)
    st_ = opt.init(params)
    b1 = make_batch(cfg, 2, 8, seed=0)
    b2 = make_batch(cfg, 2, 8, seed=1)
    batch2 = {k: np.stack([np.asarray(b1[k]), np.asarray(b2[k])])
              for k in b1}
    p1, p2 = trace_pair_step(m, params, batch2, opt=opt, opt_state=st_)
    s1, _, _ = trace_train_step(m, params, b1, opt=opt, opt_state=st_)
    s2, _, _ = trace_train_step(m, params, b2, opt=opt, opt_state=st_)
    for pair_tr, ser_tr in ((p1, s1), (p2, s2)):
        assert pair_tr.loss == pytest.approx(ser_tr.loss, rel=1e-5)
        assert pair_tr.grad_norm == pytest.approx(ser_tr.grad_norm, rel=1e-4)
        for kind in (C.KIND_ACT, C.KIND_ACT_GRAD, C.KIND_PARAM_GRAD,
                     C.KIND_MAIN_GRAD, C.KIND_PARAM_POST):
            ps, ss = pair_tr.section(kind), ser_tr.section(kind)
            assert set(ps) == set(ss)
            # post-step params pass through Adam's m/sqrt(v) normalization:
            # on the FIRST step u = g/(|g|+eps) ~= sign(g), so an element
            # whose vmapped-vs-serial gradient reassociation noise straddles
            # zero moves the update by up to 2*lr in ABSOLUTE terms — no
            # rtol absorbs that, and which elements flip varies with the
            # compile's reduction tiling (8-forced-device CPU).  Bound the
            # kind by its mathematical worst case, 2*lr (+ margin); the
            # production checker widens this kind the same way
            # (thresholds.Thresholds.kind_margins).
            atol = 2.5e-3 if kind == C.KIND_PARAM_POST else 2e-5
            for name in ps:
                np.testing.assert_allclose(
                    np.asarray(ps[name], np.float32),
                    np.asarray(ss[name], np.float32),
                    rtol=2e-4, atol=atol, err_msg=f"{kind}:{name}")
