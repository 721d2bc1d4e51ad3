"""Worker: FP round-off threshold curves + bug/FP error separation.

Reproduces (CPU-scaled) paper Fig 7 and Fig 8 on a BF16 mixed-precision GPT:

 * estimated FP round-off error per layer (input perturbed at bf16 epsilon),
   for forward activations, activation gradients and parameter gradients;
 * the actual FP error of a CORRECT tensor-parallel candidate per layer;
 * bug-induced errors for a forward bug (bug 1: wrong embedding mask) and a
   backward bug (bug 11 class: stale wgrad) per layer.

Prints TSV: section  layer  name  value   (values normalized by bf16 eps).
"""
import os
from benchmarks.common import cpu_host_devices

cpu_host_devices(os.environ, 8)

import dataclasses
import sys

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core.harness import make_model_runner
from repro.core.thresholds import (MACHINE_EPS, estimate_thresholds, rel_err)
from repro.data.synthetic import make_batch
from repro.models.model import Model
from repro.optim.adamw import AdamW
from repro.parallel.api import ParallelConfig, make_candidate_runner

EPS = MACHINE_EPS["bfloat16"]


def main():
    L = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    cfg = dataclasses.replace(
        get_config("gpt-paper").reduced(), n_layers=L, d_model=128,
        n_heads=4, n_kv_heads=4, d_ff=256, vocab=512, tie_embeddings=True,
        compute_dtype="bfloat16")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-3)
    st = opt.init(params)
    batch = make_batch(cfg, 2, 64)
    ref = make_model_runner(m, params, opt, st)

    thr, base = estimate_thresholds(ref, batch, EPS)
    pc = ParallelConfig(dp=2, tp=2)
    cand = make_candidate_runner(cfg, pc, params, opt, st)(batch)

    bug_fwd = make_candidate_runner(
        cfg, dataclasses.replace(pc, bugs=frozenset(
            ["tp_wrong_embedding_mask"])), params, opt, st)(batch)
    bug_bwd = make_candidate_runner(
        cfg, dataclasses.replace(
            pc, sp=True, bugs=frozenset(["sp_stale_wgrad"])),
        params, opt, st)(batch)

    def dump(section, getter):
        for li in range(L):
            for role, key in (("attn_out", f"layers.{li}.self_attention/output"),
                              ("mlp_out", f"layers.{li}.mlp/output")):
                v = getter(key)
                if v is not None:
                    print(f"{section}\t{li}\t{role}\t{v / EPS:.4f}")

    dump("est_act", lambda k: thr.per_tensor["activation"].get(k))
    dump("est_agrad", lambda k: thr.per_tensor["act_grad"].get(k))
    dump("dist_act",
         lambda k: rel_err(base.activations[k], cand.activations[k]))
    dump("dist_agrad",
         lambda k: rel_err(base.act_grads[k], cand.act_grads[k]))
    dump("bugfwd_act",
         lambda k: rel_err(base.activations[k], bug_fwd.activations[k]))
    dump("bugbwd_agrad",
         lambda k: rel_err(base.act_grads[k], bug_bwd.act_grads[k]))
    # param-grad estimates per layer (Fig 7c analogue)
    for li in range(L):
        k = f"layers.{li}.self_attention.linear_qkv.w"
        v = thr.per_tensor["param_grad"].get(k)
        if v is not None:
            print(f"est_pgrad\t{li}\tqkv_w\t{v / EPS:.4f}")
        print(f"bugbwd_pgrad\t{li}\tproj_w\t"
              f"{rel_err(base.param_grads[f'layers.{li}.self_attention.linear_proj.w'], bug_bwd.param_grads[f'layers.{li}.self_attention.linear_proj.w']) / EPS:.4f}")


if __name__ == "__main__":
    main()
