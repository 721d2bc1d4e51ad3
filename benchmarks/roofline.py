"""Roofline analysis (deliverable g).

XLA's cost_analysis counts while-loop bodies ONCE (verified empirically), so
full-graph numbers from the scanned/microbatched train step undercount by the
trip counts.  Instead we lower each COMPONENT unrolled — one transformer
block per segment kind (fwd+bwd for training), the embed+head+CE stem, the
optimizer step — on the production mesh with the production shardings, read
cost_analysis + collective bytes from each compiled artifact, and compose:

    total = n_micro * (sum_seg count_seg * block_cost + stem) + opt_step

Per (arch x shape), three per-device roofline terms on TPU v5e:
    compute    = FLOPs / 197e12           (bf16 MXU peak per chip)
    memory     = bytes_accessed / 819e9   (HBM bandwidth)
    collective = collective operand bytes / 50e9  (ICI per link)

plus MODEL_FLOPS = 6*N_active*D (train) or 2*N_active*D (prefill/decode) and
the useful-compute ratio.  All numbers are per device; HLO shapes are
post-SPMD (local shards), so no further division by chip count applies.
"""
from __future__ import annotations

import dataclasses
import json
import os

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
GB = 1 << 30


def run(out_json="roofline_report.json", multi_pod=False, archs=None,
        shapes=None):
    from benchmarks.common import emit, run_worker
    args = []
    if archs:
        args += ["--archs", ",".join(archs)]
    if shapes:
        args += ["--shapes", ",".join(shapes)]
    out = run_worker("benchmarks.roofline_worker", *args, devices=512,
                     timeout=7200, cpu=True)
    recs = []
    for ln in out.splitlines():
        if ln.startswith("{"):
            recs.append(json.loads(ln))
        elif ln.strip():
            print("#", ln)
    with open(out_json, "w") as f:
        json.dump(recs, f, indent=1)
    for r in recs:
        if r.get("status") != "ok":
            continue
        name = f"roofline.{r['arch']}.{r['shape']}"
        dom = r["dominant"]
        from benchmarks.common import emit
        emit(name, r["terms"][dom] * 1e6,
             f"dom={dom} c={r['terms']['compute']:.2e}s "
             f"m={r['terms']['memory']:.2e}s "
             f"x={r['terms']['collective']:.2e}s "
             f"useful={r['useful_ratio']:.2f}")
    print(f"wrote {out_json} ({len(recs)} records)")
    return recs


if __name__ == "__main__":
    run()
