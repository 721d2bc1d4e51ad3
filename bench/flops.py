"""Operations from shapes, for the per-layer metrics.

``train_flops`` counts what one training step (forward and backward) of
one side requires, by the usual convention: 2 FLOPs per multiply-add of
every weight matrix for every token, the causal half of the attention
score and value products, and three times the forward for a training
step.  Recomputation does not count.  The embedding lookup is a gather
and counts nothing; the tied output head counts as a matrix.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Weights that multiply every token, output head included."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    per_layer = d * (h + 2 * hkv) * hd + h * hd * d + 3 * d * f
    return c["num_hidden_layers"] * per_layer + d * v


def attention_flops(c: dict, seq: int) -> int:
    """Forward FLOPs of the score and value products of one causal
    sequence: each query attends to itself and the keys before it."""
    pairs = seq * (seq + 1) // 2
    hd = c["head_dim"]
    return c["num_hidden_layers"] * 2 * 2 * c["num_attention_heads"] * hd \
        * pairs


def train_flops(c: dict, batch: int, seq: int) -> int:
    """FLOPs of one training step of one side."""
    fwd = 2 * matmul_params(c) * batch * seq + attention_flops(c, seq) * batch
    return 3 * fwd

