"""Operation counts against hand counts from the published widths."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import load  # noqa: E402


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["config"]


def test_qwen3_counts():
    c = config("qwen3-1.7b")
    # qkv 2048 x (16 + 2*8)*128, out 2048 x 2048, MLP 3 x 2048 x 6144,
    # tied head 2048 x 18992
    n = 2048 * 4096 + 2048 * 2048 + 3 * 2048 * 6144 + 2048 * 18992
    assert n == 89_227_264 == flops.matmul_params(c)
    # scores and values: 2 products x 2 FLOPs x 16 heads x 128 x causal
    # pairs of 2048 tokens
    att = 2 * 2 * 16 * 128 * (2048 * 2049 // 2)
    assert att == 17_188_257_792 == flops.attention_flops(c, 2048)
    assert flops.train_flops(c, 1, 2048) == 3 * (2 * n * 2048 + att) \
        == 1_147_989_393_408


def test_qwen2_counts():
    c = config("qwen2-1.5b")
    n = 1536 * 2048 + 1536 * 1536 + 3 * 1536 * 8960 + 1536 * 18992
    assert n == 75_964_416 == flops.matmul_params(c)
    att = 2 * 2 * 12 * 128 * (2048 * 2049 // 2)
    assert flops.attention_flops(c, 2048) == att
    assert flops.train_flops(c, 2, 2048) == 3 * (2 * n * 4096 + 2 * att)


def test_parameter_counts_of_the_reference():
    ref = load.reference("qwen_dense")
    for name, norms in (("qwen3-1.7b", 3 * 2048 + 2 * 128),
                        ("qwen2-1.5b", 3 * 1536 + 2048)):
        c = config(name)
        total = sum(int(__import__("math").prod(s))
                    for s in ref.leaf_shapes(c).values())
        assert total == flops.matmul_params(c) + norms


@pytest.mark.parametrize("name", ["qwen3-1.7b", "qwen2-1.5b"])
@pytest.mark.parametrize("batch,seq", [(1, 2048), (2, 2048), (4, 512)])
def test_reference_counts_as_flops_does(name, batch, seq):
    """``supervised_mfu`` takes the count from the configuration's
    reference; for the dense reference it is ``flops.train_flops`` to the
    FLOP."""
    c = config(name)
    ref = load.reference("qwen_dense")
    assert ref.train_flops(c, batch, seq) == flops.train_flops(c, batch, seq)
