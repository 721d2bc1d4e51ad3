"""Worker (runs under 8 forced host devices): the paper's Table 1 sweep.

For every injectable silent bug: run TTrace on a clean candidate (must PASS)
and on the bug-injected candidate (must FAIL + localize).  Prints one TSV row
per bug:  bug_id  type  clean_pass  detected  localized  expected  loc_ok  secs
"""
import os
from benchmarks.common import cpu_host_devices

cpu_host_devices(os.environ, 8)

import dataclasses
import fnmatch
import sys
import time

import jax

from repro.bugs.registry import BUGS
from repro.configs.base import MoEConfig, get_config
from repro.core.harness import make_model_runner, ttrace_check
from repro.data.synthetic import make_batch
from repro.models.model import Model
from repro.optim.adamw import AdamW
from repro.parallel.api import ParallelConfig, make_candidate_runner


def pcfg_for(spec, bug_on=True):
    req = set(spec.requires)
    return ParallelConfig(
        dp=2, cp=2 if "cp" in req else 1, tp=2,
        sp=("sp" in req), zero1=("zero1" in req),
        bugs=frozenset([spec.bug_id]) if bug_on else frozenset())


def main():
    only = sys.argv[1] if len(sys.argv) > 1 else None
    base = dataclasses.replace(get_config("gpt-paper").reduced(),
                               n_layers=2, vocab=512, tie_embeddings=True)
    moe_cfg = dataclasses.replace(
        base, arch_type="moe", tie_embeddings=False,
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=128,
                      capacity_factor=0.0))
    for bid, spec in BUGS.items():
        if only and bid != only:
            continue
        if "pp" in spec.requires or "fp8" in spec.requires:
            continue   # exercised by dedicated benchmarks/tests
        t0 = time.time()
        cfg = moe_cfg if "moe" in spec.requires else base
        m = Model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        opt = AdamW(lr=1e-3)
        st = opt.init(params)
        batch = make_batch(cfg, 4, 32)
        ref = make_model_runner(m, params, opt, st)
        clean = make_candidate_runner(cfg, pcfg_for(spec, False), params,
                                      opt, st)
        buggy = make_candidate_runner(cfg, pcfg_for(spec, True), params,
                                      opt, st)
        r_clean = ttrace_check(ref, clean, batch, localize=False)
        r_buggy = ttrace_check(ref, buggy, batch, localize=True)
        loc = r_buggy.localized_module or "-"
        ok_loc = (fnmatch.fnmatchcase(loc, spec.expected_module)
                  or spec.expected_module in ("loss", "optimizer"))
        print("\t".join(map(str, [
            bid, spec.btype, r_clean.passed, not r_buggy.passed, loc,
            spec.expected_module, ok_loc, round(time.time() - t0, 1)])))
        sys.stdout.flush()


if __name__ == "__main__":
    main()
