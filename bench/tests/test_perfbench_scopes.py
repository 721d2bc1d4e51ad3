"""Device time by named scope (``trace_reduce.attribute``): on a
hand-built trace, on the recorded v5e trace, and on the HLO of a tiny
compiled training step of the program's model, with each ENTRY
instruction taken as one operation of a synthetic trace.  The seconds of
all scopes and ``other`` add up to the program's device seconds; the
``head_loss_ms`` reader and ``cell.programs()`` read them by name."""
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import cell  # noqa: E402
import load  # noqa: E402
import trace_reduce  # noqa: E402
from trace_reduce import Event, Reduced  # noqa: E402

DEV = "/device:TPU:0"
FIXTURE = os.path.join(BENCH, "tests", "fixtures",
                       "v5e_two_programs.xplane.pb")


@pytest.mark.parametrize("path,want", [
    ("jit(ref_step)/jvp(loss)/dot_general", ("loss",)),
    ("jit(cand_step)/transpose(jvp(loss))/dot_general:", ("loss",)),
    ("jit(cand_step)/transpose(jvp(attn))/bqhgd,bkhd->bhgqk/dot_general",
     ("attn", "bqhgd,bkhd->bhgqk")),
    ("jit(cand_step)/jvp(mlp)/jit(silu)/logistic", ("mlp",)),
    ("jit(cand_step)/jvp()/add", ()),
    ("jit(cand_step)/shard_map/transpose(jvp(loss))/psum", ("loss",)),
    ("jit(cand_step)/shard_map", ()),
    ("pr['layers.0.mlp/output']", ()),
    ("jit(matmul_a)/dot_general:", ()),
    ("psum", ()),
    ("", ()),
])
def test_scopes_of_an_op_path(path, want):
    assert trace_reduce.scopes(path) == want


def _synthetic(hlo: dict | None = None) -> Reduced:
    """Two steps of two programs on one chip.  The reference's second op
    is a loop whose body (two ops) runs inside it; one op has no
    metadata.  ``hlo`` is the programs' paths by instruction name."""
    ref, cand = "jit_ref_step(11)", "jit_cand_step(22)"
    red = Reduced(window=(0.0, 1.0), devices=[DEV])
    red.modules[DEV], red.ops[DEV], meta = [], [], {}
    ops = {ref: [("%fusion.1 = f", "jit(ref_step)/jvp(attn)/dot_general"),
                 ("%while.2 = w", "jit(ref_step)/jvp(mlp)/while"),
                 ("%fusion.3 = f", "jit(ref_step)/jvp(loss)/dot_general"),
                 ("%fusion.4 = f",
                  "jit(ref_step)/transpose(jvp(loss))/dot_general"),
                 ("%copy.5 = c", None)],
           cand: [("%fusion.1 = f", "jit(cand_step)/jvp(loss)/dot_general"),
                  ("%fusion.2 = f", "jit(cand_step)/optimizer/mul")]}
    t = 0.0
    for _ in range(2):
        for prog, pid in ((ref, 11), (cand, 22)):
            start = t
            for name, path in ops[prog]:
                dur = 0.04 if "while" in name else 0.01
                red.ops[DEV].append(Event(name, t, t + dur))
                if "while" in name:     # the loop body: two ops inside
                    red.ops[DEV] += [
                        Event("%fusion.9 = f", t + 0.005, t + 0.015),
                        Event("%fusion.9 = f", t + 0.02, t + 0.03)]
                    meta[(pid, "%fusion.9 = f")] = \
                        "jit(ref_step)/jvp(mlp)/dot_general"
                if path:
                    meta[(pid, name)] = path
                t += dur
            red.modules[DEV].append(Event(prog, start, t))
            t += 0.005
    red.ops[DEV].sort(key=lambda e: e.start)
    trace_reduce.attribute(red, {DEV: meta}, hlo)
    return red


def test_scope_seconds_of_a_hand_built_trace():
    red = _synthetic()
    assert red.scope_s("jit_ref_step", "loss") == pytest.approx(0.04)
    assert red.scope_s("jit_cand_step", "loss") == pytest.approx(0.02)
    assert red.scope_s("jit_ref_step", "mlp") == pytest.approx(0.08)
    assert red.scope_s("jit_ref_step", "optimizer") is None
    assert red.scope_split("jit_ref_step") == pytest.approx(
        {"attn": 0.02, "mlp": 0.08, "loss": 0.04, "other": 0.02})
    for prog, role in cell.STEP_PROGRAMS.items():
        assert sum(red.scope_split(role).values()) == \
            pytest.approx(cell.programs(red)[prog])
    # one instruction name in two programs, under two scopes
    names = dict(red.top_ops(10))
    assert names["fusion.1 [attn]"] == pytest.approx(0.02)
    assert names["fusion.1 [loss]"] == pytest.approx(0.02)
    assert names["copy.5 [other]"] == pytest.approx(0.02)


def test_hlo_paths_fill_in_ops_without_tf_op():
    """An op whose event carries no ``tf_op`` takes its path from the
    program's HLO by instruction name; one that carries it keeps it."""
    hlo = {11: {"copy.5": "jit(ref_step)/transpose(jvp(loss))/mul",
                "fusion.1": "jit(ref_step)/jvp(mlp)/dot_general"}}
    assert _synthetic(hlo).scope_split("jit_ref_step") == pytest.approx(
        {"attn": 0.02, "mlp": 0.08, "loss": 0.06})
    red = _synthetic()
    trace_reduce.attribute(red, {}, hlo)
    assert red.scope_split("jit_ref_step") == pytest.approx(
        {"mlp": 0.02, "loss": 0.02, "other": 0.12})


def test_head_loss_ms_reads_the_loss_scope_of_both_steps():
    got = load.metric("head_loss_ms").read({"trace": _synthetic(),
                                            "steps": 2})
    assert got == pytest.approx(1e3 * (0.04 + 0.02) / 2)
    red = _synthetic()
    trace_reduce.attribute(red, {})     # a trace with no op metadata
    assert red.scope_split("jit_ref_step") == pytest.approx({"other": 0.16})
    assert load.metric("head_loss_ms").read({"trace": red,
                                             "steps": 2}) is None


def test_programs_are_found_by_name_not_order():
    red = _synthetic()
    got = cell.programs(red)
    # the candidate first, and a program whose name only ends in ``_step``
    red.modules[DEV] = [Event("jit_threshold_step(5)", -0.2, -0.1)] + \
        sorted(red.modules[DEV], key=lambda e: "ref" in e.name)
    assert cell.programs(red) == got == {
        "ref_step": pytest.approx(2 * 0.08), "cand_step": pytest.approx(0.04)}


def test_recorded_trace_gives_each_op_its_path():
    with open(FIXTURE, "rb") as f:
        data = f.read()
    paths = trace_reduce.op_paths(data)
    got = sorted((pid, name.split(" = ")[0], path)
                 for pid, name, path in
                 ((k[0], k[1], v) for k, v in paths[DEV].items()))
    assert got == [(11663150706085588528, "%fusion",
                    "jit(matmul_a)/dot_general:"),
                   (15969123306974393221, "%tanh_reduce_fusion",
                    "jit(reduce_b)/reduce_sum:")]
    hlo = trace_reduce.hlo_paths(data)
    assert hlo[11663150706085588528]["fusion"] == "jit(matmul_a)/dot_general"
    assert hlo[15969123306974393221]["tanh_reduce_fusion"] == \
        "jit(reduce_b)/reduce_sum"
    assert "copy-start" not in hlo[11663150706085588528]   # no op_name
    red = trace_reduce.load(FIXTURE, "fixture.window")
    for prog in ("jit_matmul_a", "jit_reduce_b"):
        dev_s = sum(e.dur for e in red.modules[DEV] if prog in e.name)
        assert sum(red.scope_split(prog).values()) == \
            pytest.approx(dev_s, rel=1e-3)


INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = .*?\b([\w\-]+)\(")


def _entry(text: str) -> list:
    """``[(instruction line, opcode, op_name or None)]`` of the ENTRY
    computation of an HLO module's text."""
    body = text[text.index("\nENTRY"):]
    body = body[:body.index("\n}")]
    out = []
    for line in body.splitlines()[1:]:
        m = INSTR.match(line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((line.strip().split(", metadata=")[0], m.group(2),
                        op.group(1) if op else None))
    return out


@pytest.fixture(scope="module")
def tiny_step() -> tuple:
    """The optimized HLO, as text and as a serialized ``HloModuleProto``,
    of a jitted training step (loss, gradient and AdamW update) of the
    program's model at a tiny size."""
    import jax
    import numpy as np
    from repro.models.model import Model
    from repro.optim.adamw import AdamW
    c = dict(load.cell("qwen3-1.7b.unchecked")["config"]["config"],
             hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=256)
    model = Model(cell.arch_config("tiny", c))
    opt = AdamW(lr=1e-3)
    params = model.init(jax.random.key(0))
    tok = np.random.default_rng(0).integers(0, 256, (2, 32)).astype(np.int32)
    batch = {"tokens": tok, "labels": np.roll(tok, -1, 1)}

    def ref_step(p, st, b):
        loss, g = jax.value_and_grad(lambda q: model.loss(q, b)[0])(p)
        new_p, new_st, _ = opt.update(p, g, st)
        return loss, new_p, new_st
    compiled = jax.jit(ref_step).lower(params, opt.init(params), batch) \
        .compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    return compiled.as_text(), module.as_serialized_hlo_module_proto()


@pytest.fixture(scope="module")
def tiny_step_hlo(tiny_step) -> str:
    return tiny_step[0]


def test_fusions_carry_their_roots_metadata(tiny_step_hlo):
    """The trace takes a fusion's scope from the fusion's own metadata;
    XLA gives it its root instruction's."""
    comps = {}
    for block in tiny_step_hlo.split("\n\n"):
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) ", block.strip())
        root = re.search(r"^\s*ROOT .*$", block, re.M)
        if head and root:
            op = re.search(r'op_name="([^"]*)"', root.group(0))
            comps[head.group(1)] = op.group(1) if op else None
    fusions = [(line, re.search(r"calls=(%[\w.\-]+)", line).group(1), op)
               for line, code, op in _entry(tiny_step_hlo)
               if code == "fusion"]
    with_meta = [(calls, op) for _, calls, op in fusions if op is not None]
    assert len(with_meta) > 10
    assert all(comps[calls] == op for calls, op in with_meta)


def test_hlo_gives_fusions_their_bodys_path(tiny_step):
    """Where a fusion carries no metadata (its root is a tuple, a bitcast,
    a reduce-window), the program's HLO gives it a path from its body or
    the computations that calls (a reducer); an instruction that carries
    one keeps it."""
    text, proto = tiny_step
    paths = trace_reduce.module_paths(proto)
    blocks = {}
    for block in text.split("\n\n"):
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) ", block.strip())
        if head:
            blocks[head.group(1)] = block

    def reach(comp: str) -> set:
        block = blocks[comp]
        got = set(re.findall(r'op_name="(jit[^"]*)"', block))
        for sub in re.findall(r"(?:calls|to_apply)=(%[\w.\-]+)", block):
            got |= reach(sub)
        return got
    bare = 0
    for line, code, op in _entry(text):
        name = line.split(" = ")[0].lstrip("%")
        if op is not None and op.startswith("jit("):
            assert paths[name] == op
        elif code == "fusion":
            body = reach(re.search(r"calls=(%[\w.\-]+)", line).group(1))
            assert paths.get(name) in body if body else name not in paths
            bare += bool(body)
    assert bare > 0


@pytest.mark.parametrize("source", ["tf_op", "hlo"])
def test_scopes_of_a_compiled_step(tiny_step, source):
    """Each ENTRY instruction taken as one operation of a synthetic trace,
    its path from the trace's ``tf_op`` (the instruction's own metadata)
    or from the program's HLO alone."""
    text, proto = tiny_step
    skip = {"parameter", "constant", "tuple", "get-tuple-element",
            "bitcast"}
    ops = [(line, op) for line, code, op in _entry(text)
           if code not in skip]
    red = Reduced(window=(0.0, 1.0), devices=[DEV])
    red.ops[DEV] = [Event(line, i * 1e-6, (i + 1) * 1e-6)
                    for i, (line, _) in enumerate(ops)]
    red.modules[DEV] = [Event("jit_ref_step(7)", 0.0, len(ops) * 1e-6)]
    if source == "tf_op":
        trace_reduce.attribute(red, {DEV: {(7, line): op
                                           for line, op in ops if op}})
    else:
        trace_reduce.attribute(red, {}, {7: trace_reduce.module_paths(proto)})
    split = red.scope_split("jit_ref_step")
    assert {"loss", "mlp", "attn", "norm", "optimizer"} <= set(split)
    assert sum(split.values()) == pytest.approx(len(ops) * 1e-6)
    # backward operations count under their block's scope
    for scope in ("loss", "mlp", "attn"):
        fwd = sum(1 for _, op in ops if op and f"/jvp({scope})/" in op)
        bwd = sum(1 for _, op in ops
                  if op and f"/transpose(jvp({scope}))/" in op)
        assert fwd and bwd, scope
        assert red.scope_s("jit_ref_step", scope) >= (fwd + bwd) * 1e-6 \
            - 1e-12
