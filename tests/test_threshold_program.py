"""The threshold estimate's trace programs: built once per shape, they
hold no data, one serves the base run and one the perturbed run, and they
give the thresholds that two programs closing over the batch and the
perturbation gave, to the bit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import get_config
from repro.core import canonical as C
from repro.core.collector import (Trace, _make_probes, flatten_named,
                                  in_full_precision, model_loss_call,
                                  tap_shapes, trace_train_step)
from repro.core.harness import make_model_runner
from repro.core.tap import TraceContext
from repro.core.thresholds import (MACHINE_EPS, _diff_sections,
                                   estimate_thresholds,
                                   perturbed_batch_or_rewrites)
from repro.data.synthetic import make_batch
from repro.models.model import Model
from repro.optim.adamw import AdamW

KINDS = (C.KIND_ACT, C.KIND_ACT_GRAD, C.KIND_PARAM_GRAD, C.KIND_MAIN_GRAD,
         C.KIND_PARAM_POST)
EPS = MACHINE_EPS["float32"]
PROGRAM = "jit(threshold_trace)"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module")
def qwen3():
    """A tiny qwen3 layout (qk-norm GQA, tied embedding), two batches of
    one shape and different tokens."""
    cfg = dataclasses.replace(get_config("qwen3-32b").reduced(), n_layers=2,
                              vocab=256, tie_embeddings=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-3)
    batches = [make_batch(cfg, 2, 32, seed=s, step=0) for s in (0, 1)]
    assert not np.array_equal(batches[0]["tokens"], batches[1]["tokens"])
    return model, params, opt, opt.init(params), batches


class _Compiles:
    """Backend compiles by program name, from JAX's monitoring events."""

    def __init__(self):
        self.names = []

    def __call__(self, event, secs, fun_name="", **_):
        if event == COMPILE_EVENT:
            self.names.append(fun_name)

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def test_two_programs_serve_both_runs_and_every_batch(qwen3):
    model, params, opt, st, (b0, b1) = qwen3
    run = make_model_runner(model, params, opt, st)
    before = obs.table()
    with _Compiles() as first:
        estimate_thresholds(run, b0, EPS)
    with _Compiles() as second:
        estimate_thresholds(run, b1, EPS)
    # the base run and the perturbed run: one program each, for both batches
    assert obs.since(before)["counts"]["thresholds.programs"] == 2
    assert first.names.count(PROGRAM) == 2
    assert PROGRAM not in second.names


def test_batches_of_one_shape_lower_to_one_program(qwen3, monkeypatch):
    model, params, opt, st, batches = qwen3
    texts = []
    real = jax.jit

    def jit(fun, **kw):
        j = real(fun, **kw)

        def call(*a, **k):
            if fun.__name__ == "threshold_trace":
                texts.append(j.lower(*a, **k).as_text())
            return j(*a, **k)
        return call
    monkeypatch.setattr(jax, "jit", jit)
    for b in batches:
        estimate_thresholds(make_model_runner(model, params, opt, st), b,
                            EPS)
    # each runner lowers its base run, then its perturbed run
    assert len(texts) == 4
    base, perturbed = texts[0::2], texts[1::2]
    assert base[0] == base[1]
    assert perturbed[0] == perturbed[1]
    assert base[0] != perturbed[0]


def _closed_over_trace(model, params, opt, st, batch, rewrites):
    """One serial threshold run as two programs that close over the batch
    and the rewrites did it: a ``collect`` jit for the base run, a
    ``rewrite`` jit for the perturbed run, the data baked in as constants."""
    loss_call = model_loss_call(model)
    rewrites_j = (None if rewrites is None
                  else {k: jnp.asarray(v) for k, v in rewrites.items()})
    shapes, fwd_order = tap_shapes(loss_call, params, batch)
    mode = "rewrite" if rewrites_j else "collect"
    probes = _make_probes(shapes, None, True)

    def trace(p, pr):
        def loss_fn(pp, prr):
            ctx = TraceContext(mode, probes=prr, rewrites=rewrites_j or {})
            return loss_call(pp, batch, ctx), ctx.fwd
        (loss, fwd), grads = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(p, pr)
        return loss, fwd, grads

    loss, fwd, (pg, ag) = in_full_precision(jax.jit(trace))(params, probes)
    tr = Trace()
    tr.loss = float(loss)
    tr.activations = {k: fwd[k] for k in fwd_order}
    tr.act_grads = {k: ag[k] for k in fwd_order if k in ag}
    tr.param_grads = flatten_named(pg)
    new_p, _, info = in_full_precision(jax.jit(opt.update))(params, pg, st)
    tr.main_grads = flatten_named(info.main_grads)
    tr.params_post = flatten_named(new_p)
    return tr


def _bitwise_equal(a: Trace, b: Trace):
    for kind in KINDS:
        sa, sb = a.section(kind), b.section(kind)
        assert list(sa) == list(sb), kind
        for name in sa:
            np.testing.assert_array_equal(sa[name], sb[name],
                                          err_msg=f"{kind} {name}")


@pytest.mark.parametrize("seed", [0, 5])
def test_thresholds_equal_the_closed_over_programs(qwen3, seed):
    model, params, opt, st, (batch, _) = qwen3
    old_base = _closed_over_trace(model, params, opt, st, batch, None)
    _, rew = perturbed_batch_or_rewrites(batch, old_base, EPS, seed)
    assert list(rew) == ["embedding/output"]
    old_pert = _closed_over_trace(model, params, opt, st, batch, rew)
    old = _diff_sections(old_base, old_pert)

    thr, base = estimate_thresholds(make_model_runner(model, params, opt, st),
                                    batch, EPS, seed=seed)
    assert set(thr.per_tensor) == set(KINDS)
    for kind in KINDS:
        assert old[kind], kind
        assert set(thr.per_tensor[kind]) == set(old[kind]), kind
        for name, was in old[kind].items():
            assert thr.per_tensor[kind][name] == was, (kind, name)
    # the base run is the collect run, to the bit
    _bitwise_equal(base, trace_train_step(model, params, batch, opt=opt,
                                          opt_state=st)[0])
    _bitwise_equal(base, old_base)


def test_a_rewrite_of_a_tap_the_model_lacks_changes_nothing(qwen3):
    model, params, _, _, (batch, _) = qwen3
    plain, _, _ = trace_train_step(model, params, batch)
    tr, _, _ = trace_train_step(model, params, batch,
                                rewrites={"no.such.module/input": np.ones(3)})
    for name in plain.activations:
        np.testing.assert_array_equal(tr.activations[name],
                                      plain.activations[name])


@pytest.mark.parametrize("recipe", ["pp", "fp8"])
def test_recipe_runners_build_their_programs_once(qwen3, recipe):
    from repro.parallel.pp import make_pp_runner
    from repro.precision.fp8 import make_fp8_runner
    model, params, opt, st, (b0, b1) = qwen3
    if recipe == "pp":
        run = make_pp_runner(model, params, 2, opt=opt, opt_state=st)
    else:
        run = make_fp8_runner(model, params, "tile128", opt=opt,
                              opt_state=st)
    with _Compiles() as first:
        run(b0)
    with _Compiles() as second:
        tr = run(b1)
    assert first.names.count(PROGRAM) == 1
    assert PROGRAM not in second.names
    assert tr.loss != run(b0).loss


def test_a_supervised_set_up_builds_two_threshold_programs(tmp_path):
    from repro.parallel.api import ParallelConfig
    from repro.supervise import Supervisor, SuperviseConfig
    cfg = dataclasses.replace(get_config("gpt-paper").reduced(), n_layers=2,
                              vocab=256, tie_embeddings=True)
    model = Model(cfg)
    sup = Supervisor(model, cfg, ParallelConfig(), AdamW(lr=1e-3),
                     params=model.init(jax.random.PRNGKey(0)),
                     scfg=SuperviseConfig(steps=2, check_every=0,
                                          work_dir=str(tmp_path)),
                     batch_size=2, seq_len=16)
    res = sup.run()
    assert res.passed
    assert res.obs["counts"]["thresholds.programs"] == 2
