"""One run of one cell: set-up, the measured window, the comparison that
decides ``correct``, and the result.

The run drives ``repro.supervise.runner.Supervisor.run()`` once, built as
``repro.launch.supervise.main`` builds it (the program's ``Model``,
``AdamW``, ``ParallelConfig``, ``CandidateStep`` and ``SuperviseConfig``,
float32 on both sides at ``highest`` precision), from weights and batches
that the benchmark makes from the seed.  Only the program's public
interface is used: the harness builds the candidate with
``CandidateStep.build`` and hands it over wrapped (``Tap``), and hands
over its batches (``Feed``); it reads ``SuperviseResult``.

``run()`` goes ``n_warm + n`` steps.  Steps ``0 .. n_warm - 1`` are the
warm-up, part of set-up with the threshold estimate, the compiles and the
step-0 checkpoint: before each of them the candidate's step waits for its
previous call, so at most one step of each side is in flight, and the
memory read at their end is what the loop needs.  The window is the ``n``
steps after: ``n`` is ``--seconds`` over the cell's step time
(``window_step_s`` in its workload file), so every run does the same work
and the window lasts about ``--seconds``.  When the program asks for the
window's first batch the harness waits until the device has run every
step before it and the step-0 checkpoint has landed, and starts the
clock.  The window ends when ``run()`` returns: every check of its steps
resolved, the checkpoint and journal writers drained.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time
import typing
from functools import partial

import numpy as np

import checks
import load
import trace_reduce
from traffic import Batches

# both sides' jitted training steps, by the program's names for them
STEP_PROGRAMS = {"ref_step": "jit_ref_step", "cand_step": "jit_cand_step"}
WARM_STEPS = 6                      # warm-up steps before the window


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


class CompileClock:
    """Compilations and their seconds, from JAX's monitoring events."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0

        def on_duration(name, secs, **_):
            if name == self.COMPILE_EVENT:
                self.seconds += secs
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def seed_key(seed: int):
    """A PRNG key from all the bits of ``seed``."""
    import jax
    state = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(state, impl="threefry2x32")


def arch_config(name: str, c: dict, program: dict | None = None):
    """The program's ``ArchConfig`` for configuration ``c`` (Hugging Face
    key names), a dense decoder, with the configuration file's optional
    ``program`` object on top: ``ArchConfig`` keyword arguments, a nested
    object (``moe``, ``mla``, ``ssm``, ``hybrid``) built into its
    dataclass by field name.  A key that ``ArchConfig`` or its nested
    class lacks is refused."""
    from repro.configs.base import ArchConfig
    if c["rms_norm_eps"] != 1e-5 or c["torch_dtype"] != "float32":
        raise ValueError("the program runs float32 with RMSNorm eps 1e-5")
    kw = dict(
        name=name, arch_type="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], d_head=c["head_dim"],
        qk_norm=bool(c["qk_norm"]), qkv_bias=bool(c["qkv_bias"]),
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        param_dtype="float32", compute_dtype="float32", scan_layers=False)
    kw.update(_fields_of(ArchConfig, program or {}, "program"))
    return ArchConfig(**kw)


def _fields_of(cls, vals: dict, where: str) -> dict:
    """``vals`` as keyword arguments of dataclass ``cls``: an object
    given for a field whose type is a dataclass is built into it."""
    hints = typing.get_type_hints(cls)
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(vals) - names)
    if unknown:
        raise ValueError(f"{where}: {cls.__name__} has no field "
                         + ", ".join(unknown))
    out = {}
    for k, v in vals.items():
        sub = [t for t in typing.get_args(hints[k]) or (hints[k],)
               if dataclasses.is_dataclass(t)]
        out[k] = (sub[0](**_fields_of(sub[0], v, f"{where}.{k}"))
                  if sub and isinstance(v, dict) else v)
    return out


def nest(flat: dict) -> dict:
    """Dotted names to the program's parameter tree: a part that is a
    whole number indexes a list (``layers.0.mlp.gate.w``)."""
    tree: dict = {}
    for name, x in flat.items():
        parts = name.split(".")
        node = tree
        for p, nxt in zip(parts, parts[1:] + [None]):
            new = x if nxt is None else ([] if nxt.isdigit() else {})
            if isinstance(node, list):
                i = int(p)
                node.extend([None] * (i + 1 - len(node)))
                if node[i] is None:
                    node[i] = new
                node = node[i]
            else:
                node = node.setdefault(p, new)
    return tree


def flat_names(tree) -> list[str]:
    import jax
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append(".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path))
    return out


def _norms(tree):
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree.leaves(tree)])


def _diff_norms(a, b):
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x - y)))
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def window_plan(cell: dict, seconds: float) -> tuple[int, int]:
    """``(n_warm, n)``: warm-up steps and window steps.  Both are whole
    check periods, and the window's last step is a checked one."""
    period = max(1, cell["traffic"]["supervise"]["check_every"])
    n_warm = -(-WARM_STEPS // period) * period
    n = max(period, round(seconds / cell["window_step_s"] / period) * period)
    return n_warm, n


class Tap:
    """Wraps the candidate's compiled step.  It keeps the time of each
    call and, from the first three, what ``correct`` compares: the first
    gradient per tensor (Adam's first moment over ``1 - b1``) and the
    change of each tensor over the three.  While ``bounded`` it waits for
    the previous call's state before the next call."""

    def __init__(self, step, params0, b1: float, span: str | None):
        import jax
        self.step, self.p0, self.b1, self.span = step, params0, b1, span
        self.calls, self.times, self.bounded = 0, [], True
        self.grad = self.change = self.last = None
        self.names = flat_names(params0)
        self._norms = jax.jit(_norms)
        self._diff = jax.jit(_diff_norms)

    def __call__(self, p, st, batch):
        import jax
        if self.bounded:
            jax.block_until_ready((p, st))
        self.times.append(time.perf_counter())
        if self.span:
            with jax.profiler.TraceAnnotation(self.span):
                tr, p2, st2 = self.step(p, st, batch)
        else:
            tr, p2, st2 = self.step(p, st, batch)
        self.calls += 1
        if self.calls == 1:
            g = np.asarray(self._norms(st2["m"]), np.float64)
            self.grad = dict(zip(self.names, g / (1.0 - self.b1)))
        if self.calls == 3:
            d = np.asarray(self._diff(p2, self.p0), np.float64)
            self.change = dict(zip(self.names, d))
            self.p0 = None
        self.last = (p2, st2) if self.bounded else None
        return tr, p2, st2


class Feed:
    """The cell's batches, as the program asks for them.  The first ask
    for step ``start`` calls ``on_open`` before it is answered."""

    def __init__(self, batches, start: int, on_open):
        self.batches, self.start, self.on_open = batches, start, on_open
        self.opened = False

    def __call__(self, step: int) -> dict:
        if step == self.start and not self.opened:
            self.opened = True
            self.on_open()
        return self.batches(step)


def reference_run(ref_mod, c: dict, key, batches, opt: dict, precision,
                  steps: int, alter=None, freeze_from=None):
    """The plain reference over batches ``0 .. steps - 1``: every step's
    loss, the first gradient's norm per tensor and each tensor's change
    over the first three steps.  ``alter`` (control and fault readings)
    may rewrite ``(tokens, labels, mask)``; from step ``freeze_from`` on,
    the weights are left as they are (a fault reading)."""
    import jax
    import jax.numpy as jnp
    w0 = jax.jit(partial(ref_mod.init_weights, c=c))(key)
    step = jax.jit(partial(ref_mod.train_step, c=c, opt=opt,
                           precision=precision))
    names = sorted(w0)
    norms = jax.jit(lambda t: jnp.stack([jnp.sqrt(jnp.sum(t[k] * t[k]))
                                         for k in names]))
    diffs = jax.jit(lambda a, b: jnp.stack(
        [jnp.sqrt(jnp.sum((a[k] - b[k]) ** 2)) for k in names]))
    w, st, losses, grad, change = w0, ref_mod.adamw_init(w0), [], None, None
    for k in range(steps):
        b = batches(k)
        tok, lab, mask = b["tokens"], b["labels"], None
        if alter is not None:
            tok, lab, mask = alter(tok, lab)
        lval, gr, w2, st2 = step(w, st, tok, lab, mask=mask)
        if freeze_from is None or k < freeze_from:
            w, st = w2, st2
        losses.append(lval)
        if k == 0:
            grad = dict(zip(names, np.asarray(norms(gr), np.float64)))
        del gr, w2, st2
        if k == 2:
            change = dict(zip(names, np.asarray(diffs(w, w0), np.float64)))
            w0 = None
    return {"losses": [float(x) for x in losses], "grad": grad,
            "change": change}


def compare(prog: list, ref: dict, window: tuple[int, int]) -> dict:
    """The numbers of ``checks`` that compare the program's sides with the
    reference.  A side is ``{"losses", "grad", "change"}`` (``grad`` and
    ``change`` may be None: the program's reference side is read by its
    losses alone); ``window`` is the window's first and last step.  A
    number the run gave nothing to read is None."""
    w0, w1 = window
    rl = ref["losses"]

    def losses(lo, hi):
        if any(len(p["losses"]) <= hi for p in prog):
            return None
        return float(max(checks.loss_gap(p["losses"][lo:hi + 1],
                                         rl[lo:hi + 1]) for p in prog))

    tapped = [p for p in prog if p.get("grad") is not None]
    out = {"loss_gap": losses(0, 2), "loss_gap_first": losses(0, 0),
           "window_loss_gap": losses(w0, w1), "grad_gap": None,
           "change_gap": None}
    if tapped:
        skip = checks.negligible(ref["grad"])
        out["grad_gap"] = float(max(checks.norm_gap(p["grad"], ref["grad"])
                                    for p in tapped))
        if all(p.get("change") is not None for p in tapped):
            out["change_gap"] = float(max(
                checks.norm_gap(p["change"], ref["change"], skip)
                for p in tapped))
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, overrides=None,
        sabotage=None) -> dict:
    """One run; returns the result object.  ``overrides`` (tests) replaces
    parts of the cell (``load.override``); ``sabotage(sup)`` (tests)
    breaks the timed path after the ``Supervisor`` is built."""
    cell = load.override(load.cell(workload), overrides)
    c, tf = cell["config"]["config"], cell["traffic"]

    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devices[0].platform!r}")
    if require_chip and len(devices) < cell["chips"]:
        raise NoChip(f"the cell needs {cell['chips']} chips; JAX found "
                     f"{len(devices)}")
    kind = devices[0].device_kind
    peaks = load.peaks(kind) if require_chip else None

    sys.path.insert(0, os.path.join(load.ROOT, "src"))
    from repro.launch.cache import enable_compile_cache
    from repro.models.model import Model
    from repro.optim.adamw import AdamW
    from repro.parallel.api import ParallelConfig
    from repro.supervise import Supervisor, SuperviseConfig
    from repro.supervise.runner import CandidateStep

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    ref_mod = load.reference(cell["config"]["reference"])
    cfg = arch_config(cell["config"]["name"], c,
                      cell["config"].get("program"))
    model = Model(cfg)
    key = seed_key(seed)
    params0 = jax.jit(lambda k: nest(ref_mod.init_weights(k, c)))(key)
    want = jax.eval_shape(model.init, jax.random.key(0))
    if (jax.tree.structure(want) != jax.tree.structure(params0)
            or any(a.shape != b.shape for a, b in zip(
                jax.tree.leaves(want), jax.tree.leaves(params0)))):
        raise ValueError("the reference's weights do not match the "
                         "program's parameter tree")
    batches = Batches(tf, c["vocab_size"], seed)
    o, sv = tf["optimizer"], tf["supervise"]
    opt = AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"], clip=o["clip"])
    if tf["recipe"] != "dense":
        raise ValueError(f"recipe {tf['recipe']!r} is not driven here")
    pcfg = ParallelConfig(dp=tf["dp"], tp=tf["tp"])
    n_warm, n = window_plan(cell, seconds)
    work = tempfile.mkdtemp(prefix="bench_supervise_")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        cand = CandidateStep.build(cfg, pcfg, params0, opt, batches(0))
        tap = Tap(cand.step, cand.params0, o["b1"],
                  "bench.cand_step" if trace else None)
        mark: dict = {}

        def open_window():
            jax.block_until_ready(tap.last)
            tap.last, tap.bounded = None, False
            sup.keeper.flush()
            mark.update(peak=peak_bytes(devices), compile_s=clock.seconds,
                        compiles=clock.count)
            if trace:
                jax.profiler.start_trace(trace_dir)
                mark["span"] = jax.profiler.TraceAnnotation("bench.window")
                mark["span"].__enter__()
            mark["t0"] = time.perf_counter()

        scfg = SuperviseConfig(
            steps=n_warm + n, check_every=sv["check_every"],
            async_window=sv["async_window"], ckpt_every=sv["ckpt_every"],
            ckpt_keep=sv["ckpt_keep"], ring_window=sv["ring_window"],
            spill=sv["spill"], journal=sv["journal"], work_dir=work,
            seed=seed % (2 ** 31 - 1))
        sup = Supervisor(model, cfg, pcfg, opt, params=params0, scfg=scfg,
                         batch_fn=Feed(batches, n_warm, open_window),
                         candidate=dataclasses.replace(cand, step=tap),
                         log_fn=log)
        del cand
        if sabotage is not None:
            sabotage(sup)
        log(f"window {n} steps after {n_warm} warm-up steps; compile cache "
            f"{cache_dir}")

        failure, res = None, None
        try:
            res = sup.run()
        except Exception as e:   # noqa: BLE001 — reported, not correct
            failure = f"{type(e).__name__}: {e}"
            log(f"the run raised {failure[:2000]}")
        t1 = time.perf_counter()
        if trace and "span" in mark:
            mark["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
        t0 = mark.get("t0", t1)
        window_s = t1 - t0
        setup_s = t0 - t_start
        steps = max(res.steps_run - n_warm, 0) if res else 0
        peak = peak_bytes(devices)

        events = 0 if res is None else (
            len(res.watchdog_events) + len(res.degradations)
            + len(res.loud_steps) + res.checks_rescued + res.checks_lost
            + int(res.flagged))
        if res is not None and res.flagged and res.bad_check is not None:
            for r in res.bad_check.report.flagged[:12]:
                log(f"step {res.bad_check.step} flagged [{r.kind}] {r.name}"
                    f": rel_err {r.rel_err:.3e} > threshold "
                    f"{r.threshold:.3e} {r.note}")
        window_compiles = (clock.count - mark["compiles"]
                           if "compiles" in mark else None)
        gaps = np.diff(tap.times[n_warm:]) if len(tap.times) > n_warm + 1 \
            else np.zeros(1)
        slow = sorted(zip(gaps, range(n_warm + 1, len(tap.times))))[-3:]
        tail = t1 - tap.times[-1] if tap.times else 0.0
        prog = [{"losses": list(res.losses) if res else [], "grad": None,
                 "change": None},
                {"losses": list(res.cand_losses) if res else [],
                 "grad": tap.grad, "change": tap.change}]
        thresholds_s = res.timings["thresholds_s"] if res else None
        # free the program's state before the reference runs
        del sup, res, tap, params0
        gc.collect()

        ref = reference_run(ref_mod, c, key, batches, o,
                            jax.lax.Precision.HIGHEST, steps=n_warm + n)
        numbers = compare(prog, ref, (n_warm, n_warm + n - 1))
        numbers.update(verdict_events=events + (failure is not None),
                       window_compiles=window_compiles)
        limits = cell["limits"]
        correct = (failure is None and steps == n
                   and all(numbers[k] is not None and numbers[k] <= limits[k]
                           for k in limits))
        for k, v in numbers.items():
            log(f"reading {k} = {v!r}" + (f" (limit {limits[k]!r})"
                                          if k in limits else ""))

        out = {"correct": bool(correct), "attempted": n,
               "failed": n - steps + events, "metrics": {},
               "device": {"platform": devices[0].platform, "kind": kind,
                          "count": len(devices), "memory_peak_bytes": peak}}
        units = {m["name"]: m["unit"] for m in
                 cell["end_to_end"] + cell["per_layer"]}
        if not trace:
            vals = {"supervised_step_s": window_s / max(steps, 1),
                    "peak_hbm_gb": mark.get("peak", peak) / 1e9,
                    "setup_s": setup_s}
            for m in cell["end_to_end"]:
                out["metrics"][m["name"]] = {"value": vals[m["name"]],
                                             "unit": m["unit"]}
        elif "t0" in mark:
            path = next(os.path.join(d, f) for d, _, fs in
                        os.walk(trace_dir) for f in fs
                        if f.endswith(".xplane.pb"))
            red = trace_reduce.load(path, "bench.window")
            ctx = {"trace": red, "steps": max(steps, 1),
                   "window_s": window_s,
                   "flops_per_step": 2 * ref_mod.train_flops(
                       c, tf["batch"] * tf["dp"], tf["seq"]),
                   "peaks": peaks, "compile_s": mark["compile_s"],
                   "thresholds_s": thresholds_s,
                   "programs": programs(red)}
            for m in cell["per_layer"]:
                v = load.metric(m["name"]).read(ctx)
                if v is not None:
                    out["metrics"][m["name"]] = {"value": v,
                                                 "unit": units[m["name"]]}
            for role, prog in STEP_PROGRAMS.items():
                split = red.scope_split(prog)
                log(f"{prog}: device {ctx['programs'].get(role)!r} s, by "
                    "scope " + ", ".join(f"{k} {v!r}" for k, v in sorted(
                        split.items(), key=lambda kv: -kv[1])))
            out["device"].update(busy_s=red.busy_s(), window_s=red.window_s)
            out["breakdown"] = {"device_ops": red.top_ops(10),
                                "idle_gaps": red.top_gaps(10)}
        out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                         for k in limits}
        log(f"window {window_s:.3f} s, {steps} steps, setup {setup_s:.2f} s,"
            f" compile {mark.get('compile_s', clock.seconds):.2f} s, "
            f"thresholds {thresholds_s} s, set-up peak {mark.get('peak')} B,"
            f" run peak {peak} B; candidate dispatches: median gap "
            f"{np.median(gaps):.5f} s, last to window end {tail:.3f} s, "
            "longest gaps (s, step) "
            + ", ".join(f"({g:.4f}, {k})" for g, k in slow))
        return out
    finally:
        shutil.rmtree(work, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def programs(red) -> dict:
    """Device seconds by program role, averaged over the devices that ran
    it: ``ref_step`` and ``cand_step``, found by the program's names for
    the two steps (``STEP_PROGRAMS``; the trace adds a fingerprint:
    ``jit_ref_step(1234)``)."""
    per_role: dict = {}
    for dev in red.devices:
        tot: dict = {}
        for e in red.modules[dev]:
            for role, prog in STEP_PROGRAMS.items():
                if e.name.split("(", 1)[0] == prog:
                    tot[role] = tot.get(role, 0.0) + e.dur
        for role, sec in tot.items():
            per_role.setdefault(role, []).append(sec)
    return {r: sum(v) / len(v) for r, v in per_role.items()}
