import os
# a CPU simulation of the production mesh: 512 virtual host devices
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run (deliverable e).

Lowers + compiles every (architecture x input shape) pair against the
production mesh — (16,16)=("data","model") single-pod and
(2,16,16)=("pod","data","model") multi-pod — using ShapeDtypeStruct inputs
(no allocation).  Prints/collects:

  * compiled.memory_analysis()  (fits-in-HBM proof)
  * compiled.cost_analysis()    (FLOPs / bytes for the roofline)
  * collective traffic parsed from the optimized HLO

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun_report.json
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs.base import INPUT_SHAPES, get_config, list_configs
from repro.core.collector import flatten_named, unflatten_named
from repro.launch import steps as steps_mod
from repro.launch.hlo import parse_hlo_collectives
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.optim.adamw import AdamW
from repro.sharding import rules
from jax.sharding import NamedSharding, PartitionSpec as P


def _named_shardings(tree, mesh, opt_state=False):
    named = flatten_named(tree)
    shardings = rules.param_shardings(
        {k: v.shape for k, v in named.items()}, mesh, opt_state=opt_state)
    return unflatten_named(shardings, tree)


def _batch_shardings(specs: dict, mesh, batch_sharded: bool):
    out = {}
    for k, v in specs.items():
        if k == "pos" or v.ndim == 0:
            out[k] = NamedSharding(mesh, P())
            continue
        bspec = rules.batch_pspec(mesh, v.shape[0])
        entries = [bspec] + [P(None)] * (v.ndim - 1)
        spec = P(*(list(bspec) + [None] * (v.ndim - len(bspec))))
        if not batch_sharded and v.ndim >= 2 and k in ("tokens", "labels",
                                                       "features"):
            dp = rules.dp_axes(mesh)
            n = int(np.prod([mesh.shape[a] for a in dp]))
            if v.shape[1] % n == 0:
                spec = P(None, dp if len(dp) > 1 else dp[0])
        out[k] = NamedSharding(mesh, spec)
    return out


def _cache_shardings(cache_sds, mesh, batch_sharded):
    named = flatten_named(cache_sds)
    out = {}
    for name, leaf in named.items():
        spec = rules.cache_pspec(name, leaf.shape, mesh, batch_sharded,
                                 batch_dim=0 if leaf.ndim <= 2 or
                                 leaf.shape[0] > 4096 else
                                 (1 if leaf.ndim >= 3 and leaf.shape[0] <= 128
                                  else 0))
        # stacked (layer-first) caches: batch is dim 1
        out[name] = NamedSharding(mesh, spec)
    return unflatten_named(out, cache_sds)


def dryrun_pair(arch: str, shape_name: str, multi_pod: bool = False,
                verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, reason = cfg.supports_shape(shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skip",
                "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg)
    t0 = time.time()

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = _named_shardings(params_sds, mesh)
    data_sds = steps_mod.input_specs(cfg, shape)
    dp_total = int(np.prod([mesh.shape[a] for a in rules.dp_axes(mesh)]))
    batch_sharded = shape.global_batch % dp_total == 0
    b_sh = _batch_shardings(data_sds, mesh, batch_sharded)

    with rules.activate(mesh, batch_sharded):
        if shape.kind == "train":
            opt = AdamW(lr=1e-4)
            opt_sds = jax.eval_shape(opt.init, params_sds)
            o_sh = _named_shardings(opt_sds, mesh, opt_state=True)
            n_micro = steps_mod.default_n_micro(cfg, shape, dp_total)
            step = steps_mod.make_train_step(Model(cfg), opt,
                                             n_micro=n_micro)
            jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None))
            lowered = jitted.lower(params_sds, opt_sds, data_sds)
        elif shape.kind == "prefill":
            step = steps_mod.make_prefill_step(model)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params_sds, data_sds)
        else:  # decode
            cache_sds = steps_mod.cache_specs(model, shape)
            c_sh = _cache_shardings(cache_sds, mesh, batch_sharded)
            step = steps_mod.make_serve_step(model)
            jitted = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh),
                             out_shardings=(None, c_sh))
            lowered = jitted.lower(params_sds, cache_sds, data_sds)

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_hlo_collectives(compiled.as_text())
    rec = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "n_micro": (steps_mod.default_n_micro(cfg, shape, dp_total)
                    if shape.kind == "train" else 1),
        "multi_pod": multi_pod,
        "mesh": dict(zip(mesh.axis_names, [int(s) for s in
                                           np.shape(mesh.devices)])),
        "compile_s": round(time.time() - t0, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "per_device": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes": int(mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes),
        },
        "collectives": coll,
    }
    if verbose:
        gb = 1 << 30
        pd = rec["per_device"]
        print(f"[{arch} x {shape_name}{' x multipod' if multi_pod else ''}] "
              f"OK in {rec['compile_s']}s | "
              f"args {pd['argument_bytes']/gb:.2f} GiB + temp "
              f"{pd['temp_bytes']/gb:.2f} GiB per device | "
              f"flops {rec['flops']:.3e} | coll "
              f"{coll['total']['operand_bytes']/gb:.3f} GiB "
              f"({coll['total']['count']} ops)")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    pairs = []
    archs = list_configs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    records = []
    failures = 0
    for arch in archs:
        if arch == "gpt-paper" and args.all:
            continue   # paper model exercised via benchmarks, not assigned
        for shape in shapes:
            for mp in meshes:
                try:
                    records.append(dryrun_pair(arch, shape, multi_pod=mp))
                except Exception as e:
                    failures += 1
                    traceback.print_exc()
                    records.append({"arch": arch, "shape": shape,
                                    "multi_pod": mp, "status": "fail",
                                    "error": f"{type(e).__name__}: {e}"})
    n_ok = sum(1 for r in records if r["status"] == "ok")
    n_skip = sum(1 for r in records if r["status"] == "skip")
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped (documented), "
          f"{failures} FAILED")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print("wrote", args.out)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
