"""A configuration file names the program's architecture: the dense
mapping of its Hugging Face keys, with the optional ``program`` object on
top (``cell.arch_config``).  The two configurations in the benchmark give
the same ``ArchConfig`` as the dense mapping alone; a tiny mixture of
experts built from a ``program`` object runs through the harness's weight
check and one step of the candidate; an unknown field is refused."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import cell  # noqa: E402
import load  # noqa: E402
from repro.configs.base import ArchConfig, MoEConfig  # noqa: E402

DENSE = {
    "qwen3-1.7b": ArchConfig(
        name="qwen3-1.7b", arch_type="dense", n_layers=1, d_model=2048,
        n_heads=16, n_kv_heads=8, d_ff=6144, vocab=18992, d_head=128,
        qk_norm=True, qkv_bias=False, rope_theta=1e6, tie_embeddings=True,
        param_dtype="float32", compute_dtype="float32", scan_layers=False),
    "qwen2-1.5b": ArchConfig(
        name="qwen2-1.5b", arch_type="dense", n_layers=1, d_model=1536,
        n_heads=12, n_kv_heads=2, d_ff=8960, vocab=18992, d_head=128,
        qk_norm=False, qkv_bias=True, rope_theta=1e6, tie_embeddings=True,
        param_dtype="float32", compute_dtype="float32", scan_layers=False),
}
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256}
MOE = {"arch_type": "moe",
       "moe": {"n_experts": 4, "top_k": 2, "d_ff_expert": 32,
               "capacity_factor": 0.0}}


def config_file(name: str) -> dict:
    return load.cell(next(w["name"] for w in load.benchmark()["workloads"]
                          if w["config"] == name))["config"]


@pytest.mark.parametrize("name", sorted(DENSE))
def test_existing_configurations_map_as_before(name):
    body = config_file(name)
    assert "program" not in body
    got = cell.arch_config(body["name"], body["config"], body.get("program"))
    assert got == DENSE[name]


def tiny_moe():
    c = dict(config_file("qwen3-1.7b")["config"], **TINY)
    return cell.arch_config("tiny-moe", c, MOE)


def test_program_object_builds_nested_config():
    cfg = tiny_moe()
    assert cfg.arch_type == "moe" and cfg.d_model == 64
    assert cfg.moe == MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                                capacity_factor=0.0)
    # the fields the object does not name keep the dense mapping's values
    assert (cfg.qk_norm, cfg.tie_embeddings, cfg.compute_dtype) == \
        (True, True, "float32")


def test_tiny_moe_passes_the_weight_check_and_runs_a_candidate_step():
    import jax
    from repro.models.model import Model
    from repro.optim.adamw import AdamW
    from repro.parallel.api import ParallelConfig
    from repro.supervise.runner import CandidateStep
    cfg = tiny_moe()
    params = Model(cfg).init(jax.random.key(0))
    leaves = jax.tree.leaves(params)
    assert [x.shape for x in leaves if x.ndim == 3] == \
        [(4, 32, 64), (4, 64, 32), (4, 64, 32)]      # the experts
    # the harness's check: a reference that names its weights by the
    # program's dotted names nests to the program's tree
    flat = dict(zip(cell.flat_names(params), leaves))
    assert "layers.0.mlp.router" in flat
    back = cell.nest(flat)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in
               zip(jax.tree.leaves(back), leaves))
    tok = np.random.default_rng(0).integers(0, 256, (2, 32)).astype(np.int32)
    batch = {"tokens": tok, "labels": np.roll(tok, -1, 1)}
    cand = CandidateStep.build(cfg, ParallelConfig(dp=1, tp=1), back,
                               AdamW(lr=1e-3), batch)
    tr, p2, _ = cand.step(cand.params0, cand.opt_state0, batch)
    assert np.isfinite(float(tr.loss))
    moved = jax.tree.map(lambda a, b: float(abs(a - b).max()),
                         cand.params0, p2)
    assert moved["layers"][0]["mlp"]["experts"]["up"] > 0


def test_nest_makes_lists_of_numbered_parts():
    got = cell.nest({"dense_layers.0.mlp.w": 1, "layers.1.norm": 2,
                     "layers.0.norm": 3, "final_norm": 4})
    assert got == {"dense_layers": [{"mlp": {"w": 1}}],
                   "layers": [{"norm": 3}, {"norm": 2}], "final_norm": 4}


@pytest.mark.parametrize("program", [
    {"n_expert": 4},                                  # no such field
    {"arch_type": "moe", "moe": {"n_experts": 4, "experts_per_tok": 2}},
])
def test_unknown_program_field_is_refused(program):
    c = config_file("qwen3-1.7b")["config"]
    with pytest.raises(ValueError, match="has no field"):
        cell.arch_config("x", c, program)


def test_eps_and_dtype_are_still_checked():
    c = dict(config_file("qwen3-1.7b")["config"], rms_norm_eps=1e-6)
    with pytest.raises(ValueError, match="eps"):
        cell.arch_config("x", c, MOE)
