"""The one traffic generator: a training job's batches from a traffic
file and ``--seed``.

A traffic file (``bench/traffic/<name>.json``) fixes the job: ``batch``
rows of ``seq`` tokens per step, the token distribution, the candidate
recipe and mesh, the supervision policy and the optimizer.  Tokens follow
a Zipf law over the configuration's vocabulary: rank ``r`` has probability
proportional to ``r ** -exponent``, and ranks map to ids through a
permutation drawn from the seed.  Step ``k`` of seed ``s`` is the same
batch in every run, so the reference sees exactly what the program saw,
and every seed gives the same shapes and the same work.
"""
from __future__ import annotations

import numpy as np


class Batches:
    """``batches(step) -> {"tokens": (batch, seq) int32, "labels": ...}``;
    labels are the tokens shifted by one."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        dist = traffic["tokens"]
        if dist["distribution"] != "zipf":
            raise ValueError(f"unknown token distribution {dist!r}")
        self.batch, self.seq, self.seed = traffic["batch"], traffic["seq"], seed
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -dist["exponent"]
        self.cdf = np.cumsum(p / p.sum())
        self.ids = np.random.default_rng([seed, 1 << 40]).permutation(
            vocab).astype(np.int32)
        self.vocab = vocab

    def __call__(self, step: int) -> dict:
        u = np.random.default_rng([self.seed, step]).random(
            (self.batch, self.seq + 1))
        ranks = np.minimum(np.searchsorted(self.cdf, u), self.vocab - 1)
        toks = self.ids[ranks]
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
