"""Trace collector (paper §4.3): runs one training iteration and records

* forward activations of every tapped module (inputs + outputs),
* activation gradients (via zero probes — the functional tensor-hook),
* parameter gradients,
* main (fp32, post-clip) gradients from the optimizer,
* post-step parameters,

as a ``Trace`` whose sections are **lazily device-resident**: leaves stay
``jax.Array`` until something explicitly asks for numpy (``section[name]``
or ``.host()``).  The batched checker (core.relerr_engine) reads the raw
leaves, so a full equivalence check never transfers activations that pass —
only N x 2 reduction scalars cross the device boundary.
"""
from __future__ import annotations

from collections.abc import MutableMapping
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.tap import TraceContext


def device_ctx(device):
    """``jax.default_device`` context for ``device`` (no-op when None).

    Computations dispatched inside stay UNCOMMITTED on ``device`` — they run
    there, yet downstream consumers (the differential check's reduction over
    reference AND candidate leaves) remain free to place the consuming
    computation wherever its other operands are committed.  This is how the
    supervisor partitions the reference step onto its own device set without
    ever producing a mixed-committed-device dispatch error.
    """
    return jax.default_device(device) if device is not None else nullcontext()


def full_precision():
    """Context in which float32 matmuls run at full float32 precision.

    The thresholds assume float32's epsilon, but a TPU runs float32 matmuls
    as bfloat16 passes by default.  The precision is fixed when a function
    is traced, so entering this around the CALL of a jitted step is enough
    (it is part of the jit cache key)."""
    return jax.default_matmul_precision("highest")


def in_full_precision(fn):
    """``fn`` called under ``full_precision()``."""
    def wrapped(*args, **kwargs):
        with full_precision():
            return fn(*args, **kwargs)
    return wrapped


# ---------------------------------------------------------------------------
# pytree <-> flat named dict
# ---------------------------------------------------------------------------

def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def flatten_named(tree, sep=".") -> dict[str, jax.Array]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {sep.join(_key_str(k) for k in path): leaf for path, leaf in flat}


def unflatten_named(names: dict, template):
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in flat:
        leaves.append(names[".".join(_key_str(k) for k in path)])
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Trace
# ---------------------------------------------------------------------------

class Section(MutableMapping):
    """One trace kind: an ordered name -> tensor mapping with a lazy host
    boundary.

    Leaves are stored as handed in (``jax.Array`` or numpy).  ``sec[name]``
    / ``.items()`` materialize numpy (cached); ``.raw(name)`` /
    ``.raw_items()`` return the stored leaf without any transfer — the
    contract the batched checker relies on.
    """
    __slots__ = ("_data", "_host")

    def __init__(self, data=None):
        if isinstance(data, Section):
            self._data = dict(data._data)
            self._host = dict(data._host)
        else:
            self._data = dict(data) if data else {}
            self._host = {}

    # ---- lazy host access --------------------------------------------------
    def __getitem__(self, name) -> np.ndarray:
        h = self._host.get(name)
        if h is None:
            h = self._host[name] = np.asarray(self._data[name])
        return h

    def __setitem__(self, name, value):
        self._data[name] = value
        self._host.pop(name, None)

    def __delitem__(self, name):
        del self._data[name]
        self._host.pop(name, None)

    def __iter__(self):
        return iter(self._data)

    def __len__(self):
        return len(self._data)

    def __contains__(self, name):
        return name in self._data

    def __repr__(self):
        return f"Section({list(self._data)!r})"

    # ---- device access -----------------------------------------------------
    def raw(self, name):
        """The stored leaf — no host transfer."""
        return self._data[name]

    def raw_items(self):
        return self._data.items()

    def shape_of(self, name) -> tuple:
        return tuple(self._data[name].shape)

    def host(self) -> dict[str, np.ndarray]:
        """Materialize every leaf to numpy (one explicit bulk transfer)."""
        return {name: self[name] for name in self._data}

    # ---- per-microbatch sections -------------------------------------------
    @classmethod
    def concat(cls, sections, axis: int = 0) -> "Section":
        """Concatenate same-named sections along ``axis`` — the microbatch
        axis of per-rank pipeline traces — without any host transfer
        (leaves stay device-resident; the merger's per-rank path builds
        the reference-shaped sections this way)."""
        secs = [s if isinstance(s, Section) else cls(s) for s in sections]
        if not secs:
            return cls()
        names = list(secs[0])
        for s in secs[1:]:
            if list(s) != names:
                raise ValueError(
                    "per-microbatch sections disagree on tensor names")
        out = cls()
        for n in names:
            out[n] = jnp.concatenate([s.raw(n) for s in secs], axis=axis)
        return out


_SECTION_FIELDS = ("activations", "act_grads", "param_grads", "main_grads",
                   "params_post")


@dataclass
class Trace:
    activations: Section = field(default_factory=Section)
    act_grads: Section = field(default_factory=Section)
    param_grads: Section = field(default_factory=Section)
    main_grads: Section = field(default_factory=Section)
    params_post: Section = field(default_factory=Section)
    loss: float = float("nan")
    grad_norm: float = float("nan")
    meta: dict = field(default_factory=dict)

    def __setattr__(self, name, value):
        # plain dicts (tests, ad-hoc traces) are adopted into lazy Sections
        if name in _SECTION_FIELDS and not isinstance(value, Section):
            value = Section(value)
        object.__setattr__(self, name, value)

    def section(self, kind: str) -> Section:
        from repro.core import canonical as C
        return {C.KIND_ACT: self.activations, C.KIND_ACT_GRAD: self.act_grads,
                C.KIND_PARAM_GRAD: self.param_grads,
                C.KIND_MAIN_GRAD: self.main_grads,
                C.KIND_PARAM_POST: self.params_post}[kind]

    @property
    def nbytes(self) -> int:
        """Bytes of every section's leaves, from their shapes alone (no
        transfer, no device sync)."""
        return sum(int(x.nbytes) for f in _SECTION_FIELDS
                   for _, x in getattr(self, f).raw_items())

    def host(self) -> "Trace":
        """Force every section to host numpy (explicit bulk transfer)."""
        for f in _SECTION_FIELDS:
            getattr(self, f).host()
        return self


# ---------------------------------------------------------------------------
# Reference collector (single-device)
# ---------------------------------------------------------------------------

def tap_shapes(loss_callable, params, batch) -> tuple[dict, list[str]]:
    """Pass 1: eval_shape the forward to enumerate tap names/shapes.

    Also returns the tap names in FORWARD ORDER (jax sorts dict pytrees, but
    propagation-order bug localization needs execution order)."""
    order: list[str] = []

    def f(params):
        ctx = TraceContext("collect")
        loss_callable(params, batch, ctx)
        order.clear()
        order.extend(ctx.fwd.keys())
        return ctx.fwd

    return jax.eval_shape(f, params), order


def trace_train_step(model, params, batch, opt=None, opt_state=None,
                     rewrites: Optional[dict] = None,
                     collect_act_grads: bool = True,
                     tap_filter: Optional[Callable[[str], bool]] = None,
                     jit: bool = True) -> tuple[Trace, dict, Optional[dict]]:
    """Run ONE training iteration of the single-device reference with full
    trace collection.  Returns (trace, new_params, new_opt_state).

    ``rewrites``: {tap_name: np/jnp array} — overwrite module inputs
    (localization mode / threshold estimation).
    """
    return trace_fn_step(model_loss_call(model), params, batch, opt=opt,
                         opt_state=opt_state, rewrites=rewrites,
                         collect_act_grads=collect_act_grads,
                         tap_filter=tap_filter, jit=jit)


def model_loss_call(model):
    """``loss_call(params, batch, ctx) -> loss`` of a zoo ``Model``."""
    def loss_call(p, b, ctx):
        loss, _ = model.loss(p, b, ctx=ctx)
        return loss
    return loss_call


def _make_probes(shapes, tap_filter, collect_act_grads):
    if not collect_act_grads:
        return {}
    return {k: jnp.zeros(s.shape, jnp.float32)
            for k, s in shapes.items()
            if (tap_filter is None or tap_filter(k))
            and jnp.issubdtype(s.dtype, jnp.floating)}


def make_trace_collector(loss_call, opt=None, collect_act_grads=True,
                         tap_filter=None, jit=True):
    """Build ``collect(params, batch, opt_state=None, rewrites=None) ->
    (Trace, new_params, new_opt_state)``: one training iteration with full
    trace collection.

    Data reach the compiled program only as arguments — the batch, the
    zero probes and the rewrites — so two batches of one shape run one
    program, and a new process finds it in the persistent compile cache.
    The set of rewritten taps picks the program: a plain run and a run
    that rewrites ``embedding/output`` (the threshold estimate's two runs)
    are two programs.  Tap discovery runs once per batch shape.
    """
    def threshold_trace(p, b, pr, rw):
        obs.count("thresholds.programs")

        def loss_fn(pp, prr):
            ctx = TraceContext("rewrite" if rw else "collect", probes=prr,
                               rewrites=rw)
            return loss_call(pp, b, ctx), ctx.fwd
        (loss, fwd), (pgrads, agrads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(p, pr)
        return loss, fwd, pgrads, agrads

    trace_c = in_full_precision(jax.jit(threshold_trace) if jit
                                else threshold_trace)
    upd_c = None
    if opt is not None:
        upd_c = in_full_precision(jax.jit(opt.update) if jit else opt.update)
    layouts = {}    # batch signature -> (tap shapes, forward order)

    def collect(params, batch, opt_state=None, rewrites=None):
        b = jax.tree.map(jnp.asarray, batch)
        leaves, treedef = jax.tree.flatten(b)
        key = (treedef, tuple((x.shape, x.dtype) for x in leaves))
        if key not in layouts:
            layouts[key] = tap_shapes(loss_call, params, b)
        shapes, fwd_order = layouts[key]
        # a rewrite of a tap the model lacks has no effect
        rw = {k: jnp.asarray(v) for k, v in (rewrites or {}).items()
              if k in shapes}
        probes = _make_probes(shapes, tap_filter, collect_act_grads)
        loss, fwd, pgrads, agrads = trace_c(params, b, probes, rw)
        del rw, probes      # not held while the update runs

        tr = Trace()
        tr.loss = float(loss)
        tr.activations = {k: fwd[k] for k in fwd_order}
        tr.act_grads = {k: agrads[k] for k in fwd_order if k in agrads}
        tr.param_grads = flatten_named(pgrads)
        tr.meta["fwd_order"] = list(fwd_order)

        new_params, new_state = params, opt_state
        if upd_c is not None:
            new_params, new_state, info = upd_c(params, pgrads, opt_state)
            tr.main_grads = flatten_named(info.main_grads)
            tr.params_post = flatten_named(new_params)
            tr.grad_norm = float(info.grad_norm)
        return tr, new_params, new_state

    return collect


def trace_fn_step(loss_call, params, batch, opt=None, opt_state=None,
                  rewrites=None, collect_act_grads=True, tap_filter=None,
                  jit=True) -> tuple[Trace, dict, Optional[dict]]:
    """Generic collector over any ``loss_call(params, batch, ctx) -> loss``.

    Used for both the reference model and candidate step functions that
    compute loss differently (e.g. pipeline-partitioned execution).  One
    call of a ``make_trace_collector`` collector; a runner over many
    batches builds the collector once instead.
    """
    collect = make_trace_collector(loss_call, opt,
                                   collect_act_grads=collect_act_grads,
                                   tap_filter=tap_filter, jit=jit)
    return collect(params, batch, opt_state, rewrites)


# ---------------------------------------------------------------------------
# Once-compiled stateful trace step (the supervisor's lockstep contract)
# ---------------------------------------------------------------------------

def make_trace_step(loss_call, opt, params, batch,
                    collect_act_grads: bool = True, tap_filter=None,
                    jit: bool = True, device=None, name: str = "ref_step"):
    """Build a trace-collecting FULL train step compiled exactly once.

    ``trace_train_step`` re-traces every call (fresh closures -> fresh jit
    cache entries); a multi-step supervised run cannot afford that.  This
    builder runs tap discovery once against the template ``(params, batch)``
    shapes and returns ``step(params, opt_state, batch) -> (Trace,
    new_params, new_opt_state)`` backed by a single jitted callable —
    every subsequent same-shaped call is a cache hit.

    The returned Trace's sections are lazily device-resident (collector
    contract) and ``trace.loss`` / ``trace.grad_norm`` are left as device
    scalars so the caller's pipeline is never forced to synchronize.

    ``device`` places the step (and its probe constants) on a specific
    device as an UNCOMMITTED default — the supervisor's disjoint
    reference-device set, so reference and candidate steps dispatched
    back-to-back run concurrently.

    ``name`` names the compiled program (``jit_<name>``): the reference's
    step is ``ref_step``; a candidate recipe built on this step passes
    ``cand_step``.
    """
    shapes, fwd_order = tap_shapes(loss_call, params, batch)
    with device_ctx(device):
        probes = _make_probes(shapes, tap_filter, collect_act_grads)

    def _step(p, st, b, pr):
        def loss_fn(pp, prr):
            ctx = TraceContext("collect", probes=prr, rewrites={})
            loss = loss_call(pp, b, ctx)
            return loss, ctx.fwd
        (loss, fwd), (pgrads, agrads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(p, pr)
        new_p, new_st, info = opt.update(p, pgrads, st)
        return (loss, fwd, pgrads, agrads, new_p, new_st,
                info.main_grads, info.grad_norm)

    _step.__name__ = _step.__qualname__ = name
    step_c = jax.jit(_step) if jit else _step

    def step(p, st, b):
        with device_ctx(device), full_precision():
            (loss, fwd, pgrads, agrads, new_p, new_st,
             main_grads, grad_norm) = step_c(p, st, b, probes)
        tr = Trace()
        tr.loss = loss
        tr.grad_norm = grad_norm
        tr.activations = {k: fwd[k] for k in fwd_order}
        tr.act_grads = {k: agrads[k] for k in fwd_order if k in agrads}
        tr.param_grads = flatten_named(pgrads)
        tr.main_grads = flatten_named(main_grads)
        tr.params_post = flatten_named(new_p)
        tr.meta["fwd_order"] = list(fwd_order)
        return tr, new_p, new_st

    return step


# ---------------------------------------------------------------------------
# Fused pair collector (threshold estimation in one compiled call)
# ---------------------------------------------------------------------------

def trace_pair_step(model, params, batch2, opt=None, opt_state=None,
                    collect_act_grads: bool = True, tap_filter=None,
                    jit: bool = True) -> tuple[Trace, Trace]:
    """Collect traces of TWO batches (stacked on a leading axis of size 2 in
    every leaf of ``batch2``) in ONE vmapped, compiled step — the fused path
    of threshold estimation: base and eps-perturbed reference run together
    instead of two serial jit round-trips.
    """
    return trace_fn_pair(model_loss_call(model), params, batch2, opt=opt,
                         opt_state=opt_state,
                         collect_act_grads=collect_act_grads,
                         tap_filter=tap_filter, jit=jit)


def make_pair_collector(loss_call, opt, params, batch, *,
                        collect_act_grads=True, tap_filter=None, jit=True,
                        row_rewrite=None, device=None):
    """Build-once vmapped BASE+PERTURBED pair collection — the single
    source of the stacked two-row reference run.

    ``trace_fn_pair`` calls it once per invocation; the supervised loop's
    ``thresholds.make_pair_estimator`` builds it once and reuses the same
    compiled callable across re-estimation epochs.  ``batch`` is an
    UNSTACKED shape template.  ``row_rewrite(flag, step)`` optionally
    builds a per-row callable-rewrite dict traced into the vmapped step
    (the token-input embedding perturbation: flag 0 on the base row, 1 on
    the perturbed row).

    Returns ``collect(params, opt_state, batch2, step=0) -> (Trace,
    Trace)`` with ``collect.shapes`` / ``collect.fwd_order`` exposing the
    tap discovery; loss/grad_norm stay device scalars (callers that need
    host floats convert).
    """
    batch_t = {k: jnp.asarray(v) for k, v in batch.items()}
    shapes, fwd_order = tap_shapes(loss_call, params, batch_t)
    with device_ctx(device):
        probes = _make_probes(shapes, tap_filter, collect_act_grads)

    def one(p, b, flag, step_k, pr):
        def loss_fn(pp, prr):
            rew = row_rewrite(flag, step_k) if row_rewrite is not None else {}
            ctx = TraceContext("rewrite" if rew else "collect", probes=prr,
                               rewrites=rew)
            loss = loss_call(pp, b, ctx)
            return loss, ctx.fwd
        (loss, fwd), (pg, ag) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(p, pr)
        return loss, fwd, pg, ag

    def threshold_pair(p, st, b2, flags, step_k, pr):
        loss, fwd, pg, ag = jax.vmap(
            one, in_axes=(None, 0, 0, None, None))(p, b2, flags, step_k, pr)
        if opt is None:
            return loss, fwd, pg, ag, None, None, None
        new_p, _, info = jax.vmap(
            opt.update, in_axes=(None, 0, None))(p, pg, st)
        return loss, fwd, pg, ag, info.main_grads, new_p, info.grad_norm

    pair_c = jax.jit(threshold_pair) if jit else threshold_pair
    flags = jnp.asarray([0.0, 1.0], jnp.float32)

    def collect(p, st, batch2, step: int = 0) -> tuple[Trace, Trace]:
        with device_ctx(device), full_precision():
            b2 = {k: jnp.asarray(v) for k, v in batch2.items()}
            loss, fwd, pg, ag, mg, new_p, gn = pair_c(p, st, b2, flags,
                                                      jnp.int32(step), probes)
        pg_named = flatten_named(pg)
        mg_named = None if mg is None else flatten_named(mg)
        np_named = None if new_p is None else flatten_named(new_p)
        traces = []
        for i in (0, 1):
            tr = Trace()
            tr.loss = loss[i]
            tr.activations = {k: fwd[k][i] for k in fwd_order}
            tr.act_grads = {k: ag[k][i] for k in fwd_order if k in ag}
            tr.param_grads = {k: v[i] for k, v in pg_named.items()}
            tr.meta["fwd_order"] = list(fwd_order)
            if mg_named is not None:
                tr.main_grads = {k: v[i] for k, v in mg_named.items()}
                tr.params_post = {k: v[i] for k, v in np_named.items()}
                tr.grad_norm = gn[i]
            traces.append(tr)
        return traces[0], traces[1]

    collect.shapes = shapes
    collect.fwd_order = fwd_order
    return collect


def trace_fn_pair(loss_call, params, batch2, opt=None, opt_state=None,
                  collect_act_grads=True, tap_filter=None, jit=True
                  ) -> tuple[Trace, Trace]:
    batch2_j = {k: jnp.asarray(v) for k, v in batch2.items()}
    batch0 = {k: v[0] for k, v in batch2_j.items()}
    collect = make_pair_collector(loss_call, opt, params, batch0,
                                  collect_act_grads=collect_act_grads,
                                  tap_filter=tap_filter, jit=jit)
    st = None
    if opt is not None:
        st = opt_state if opt_state is not None else opt.init(params)
    t0, t1 = collect(params, st, batch2_j)
    for tr in (t0, t1):      # one-shot API contract: host floats
        tr.loss = float(tr.loss)
        if opt is not None:
            tr.grad_norm = float(tr.grad_norm)
    return t0, t1
