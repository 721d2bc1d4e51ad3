"""The program's spans and counters (``repro.obs``), the names of its
compiled programs and their named scopes, and what one supervised run
records."""
import dataclasses
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
# imported before any test patches ``jax.jit``: their module-level jitted
# functions must stay jax's own
from repro import supervise  # noqa: F401
from repro.core import relerr_engine
from repro.kernels import (flash_attention, fp8_matmul,  # noqa: F401
                           relerr, ssm_scan)


def _small_setup():
    from repro.configs.base import get_config
    from repro.models.model import Model
    from repro.optim.adamw import AdamW
    cfg = dataclasses.replace(get_config("gpt-paper").reduced(),
                              n_layers=2, vocab=256, tie_embeddings=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, AdamW(lr=1e-3)


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------

def test_spans_nest_and_other_threads_share_the_table():
    before = obs.table()

    def writer():
        with obs.span("test.thread", step=1):
            obs.count("test.thread_bytes", 10)

    with obs.span("test.outer", step=3) as outer:
        with obs.span("test.inner") as inner:
            t = threading.Thread(target=writer)
            t.start()
            t.join()
    d = obs.since(before)
    for name in ("test.outer", "test.inner", "test.thread"):
        assert d["spans"][name]["count"] == 1
    assert 0 < inner.seconds <= outer.seconds
    assert d["spans"]["test.outer"]["total_s"] == outer.seconds
    assert d["spans"]["test.outer"]["max_s"] == outer.seconds
    assert d["counts"]["test.thread_bytes"] == 10
    # a second span adds to the count and total and keeps the maximum
    with obs.span("test.inner") as again:
        pass
    d = obs.since(before)
    assert d["spans"]["test.inner"]["count"] == 2
    assert d["spans"]["test.inner"]["total_s"] == pytest.approx(
        inner.seconds + again.seconds)
    assert d["spans"]["test.inner"]["max_s"] == max(inner.seconds,
                                                    again.seconds)


def test_no_update_is_lost_across_threads():
    import os
    import sys
    before = obs.table()
    n_threads, n = 4 * (os.cpu_count() or 2), 500
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(i):
            for _ in range(n):
                with obs.span("test.stress"):
                    obs.count("test.stress_n")
                obs.high("test.stress_high", i)
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    d = obs.since(before)
    assert d["spans"]["test.stress"]["count"] == n_threads * n
    assert d["counts"]["test.stress_n"] == n_threads * n
    assert d["highs"]["test.stress_high"] == n_threads - 1


def test_counters_and_high_water_marks_add_up():
    before = obs.table()
    for n in (1, 2, 3):
        obs.count("test.bytes", n)
    for v in (2, 5, 1):
        obs.high("test.depth", v)
    d = obs.since(before)
    assert d["counts"]["test.bytes"] == 6
    assert d["highs"]["test.depth"] == 5
    assert "test.bytes" not in obs.since(obs.table())["counts"]
    text = obs.report(d)
    assert "test.bytes" in text and "test.depth" in text


# ---------------------------------------------------------------------------
# program names and named scopes
# ---------------------------------------------------------------------------

@pytest.fixture
def lowered(monkeypatch):
    """Every program ``jax.jit`` builds while live, by function name: the
    lowered text of its first call, with op locations."""
    seen = {}
    real = jax.jit

    def jit(fun, **kw):
        j = real(fun, **kw)

        def call(*a, **k):
            if fun.__name__ not in seen:
                seen[fun.__name__] = j.lower(*a, **k).as_text(
                    debug_info=True)
            return j(*a, **k)
        return call
    monkeypatch.setattr(jax, "jit", jit)
    return seen


def _module(text: str) -> str:
    return re.search(r"module @(\S+)", text).group(1)


def test_supervised_programs_are_named(lowered, tmp_path):
    from repro.parallel.api import ParallelConfig
    from repro.supervise import Supervisor, SuperviseConfig
    cfg, model, params, opt = _small_setup()
    sup = Supervisor(model, cfg, ParallelConfig(), opt, params=params,
                     scfg=SuperviseConfig(steps=3, reestimate_every=2,
                                          work_dir=str(tmp_path)),
                     batch_size=2, seq_len=16)
    assert sup.run().passed
    names = {_module(t) for t in lowered.values()}
    assert {"jit_ref_step", "jit_cand_step", "jit_threshold_pair"} <= names
    a = [jnp.ones((300,)), jnp.ones((5, 7))]
    b = [x * 1.5 for x in a]
    names |= {_module(relerr_engine.relerr_packed.lower(a, b).as_text()),
              _module(relerr_engine.relerr_fused.lower(a, b).as_text())}
    assert {"jit_relerr_packed", "jit_relerr_fused"} <= names
    # the benchmark finds both sides' steps by this: nothing else the
    # supervised loop dispatches may carry it
    assert {n for n in names if "_step" in n} == {"jit_ref_step",
                                                  "jit_cand_step"}
    # named scopes label each step's operations by block, forward
    # (``jvp(attn)/dot_general``), backward and update alike
    for side in ("ref_step", "cand_step"):
        for scope in ("embed", "attn", "mlp", "norm", "loss", "optimizer"):
            assert re.search(rf"[/(]{scope}\)?/", lowered[side]), (side,
                                                                   scope)


def test_threshold_trace_program_is_named(lowered):
    from repro.core.collector import trace_fn_step
    cfg, model, params, opt = _small_setup()
    from repro.data.synthetic import make_batch
    batch = make_batch(cfg, 2, 16, seed=0, step=0)
    trace_fn_step(lambda p, b, ctx: model.loss(p, b, ctx=ctx)[0], params,
                  batch, opt=opt, opt_state=opt.init(params))
    assert _module(lowered["threshold_trace"]) == "jit_threshold_trace"
    assert "_step" not in _module(lowered["update"])


# ---------------------------------------------------------------------------
# one supervised run
# ---------------------------------------------------------------------------

def _trace_bytes(model, params, batch) -> int:
    """Both sides' trace bytes of one step, from abstract shapes: three
    parameter-sized sections (gradients, fp32 main gradients, updated
    parameters) and every tap's activation and, for float taps, its
    gradient."""
    from repro.core.collector import tap_shapes
    p_abs = jax.eval_shape(lambda: params)
    leaves = jax.tree.leaves(p_abs)
    pbytes = sum(x.size * x.dtype.itemsize for x in leaves)
    main = sum(x.size * 4 for x in leaves)
    taps, _ = tap_shapes(lambda p, b, ctx: model.loss(p, b, ctx=ctx)[0],
                         params, batch)
    act = sum(x.size * x.dtype.itemsize for x in taps.values())
    act_grad = sum(x.size * 4 for x in taps.values()
                   if jnp.issubdtype(x.dtype, jnp.floating))
    return 2 * (2 * pbytes + main + act + act_grad)


@pytest.mark.parametrize("checks", ["off", "sync"])
def test_supervised_run_records_its_spans_and_counters(tmp_path, monkeypatch,
                                                       checks):
    from repro.data.synthetic import make_batch
    from repro.parallel.api import ParallelConfig
    from repro.supervise import Supervisor, SuperviseConfig
    opened = []
    real = obs.span

    def span(name, **args):
        opened.append((name, args))
        return real(name, **args)
    monkeypatch.setattr(obs, "span", span)
    cfg, model, params, opt = _small_setup()
    steps, every = 6, 4
    scfg = SuperviseConfig(steps=steps, ckpt_every=every, spill=False,
                           check_every=0 if checks == "off" else 1,
                           async_window=0, work_dir=str(tmp_path))
    sup = Supervisor(model, cfg, ParallelConfig(), opt, params=params,
                     scfg=scfg, batch_size=2, seq_len=16)
    res = sup.run()
    assert res.passed and res.steps_run == steps
    o = res.obs
    assert o["spans"]["supervise.step"]["count"] == steps
    assert [a["step"] for n, a in opened
            if n == "supervise.step"] == list(range(steps))
    assert o["spans"]["ckpt.write"]["count"] == -(-steps // every)
    assert o["counts"]["ring.puts"] == steps
    modes = [a["mode"] for n, a in opened if n == "supervise.check"]
    assert modes == ["poll" if checks == "off" else "sync"] * steps
    assert ("check.bytes" in o["counts"]) == (checks == "sync")
    for name in ("supervise.batch", "supervise.ref_dispatch",
                 "supervise.cand_dispatch", "supervise.ring_put"):
        assert o["spans"][name]["count"] == steps
    assert o["spans"]["supervise.thresholds"]["count"] == 1
    assert o["counts"]["journal.records"] == sup.journal.appended
    assert o["counts"]["ckpt.bytes"] > 0
    # the trace bytes are what the shapes say, from no device sync
    batch = make_batch(cfg, 2, 16, seed=0, step=0)
    assert o["counts"]["ring.trace_bytes"] == steps * _trace_bytes(
        model, params, batch)
    # the timings that stay are read from the spans
    assert set(res.timings) == {"thresholds_s", "steady_steps_per_s"}
    assert res.timings["thresholds_s"] == \
        o["spans"]["supervise.thresholds"]["total_s"]
    assert res.timings["steady_steps_per_s"] > 0
    if checks == "off":
        # a second run's obs is its own change, not the process's total
        sup2 = Supervisor(model, cfg, ParallelConfig(), opt, params=params,
                          scfg=dataclasses.replace(
                              scfg, work_dir=str(tmp_path / "again")),
                          batch_size=2, seq_len=16)
        res2 = sup2.run()
        assert res2.obs["spans"]["supervise.step"]["count"] == steps
        assert res2.obs["counts"]["ring.puts"] == steps
        assert obs.table()["counts"]["ring.puts"] >= 2 * steps
    assert np.isfinite(res.losses).all()
