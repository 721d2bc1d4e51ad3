"""Roofline worker (512 forced host devices): component-wise lowering.

Lowers each block kind / stem / optimizer unrolled on the production mesh,
reads cost_analysis + collective bytes, composes totals per (arch x shape),
prints one JSON record per line.  See benchmarks.roofline for the method.
"""
import os

from benchmarks.common import cpu_host_devices

cpu_host_devices(os.environ, 512)

import argparse
import dataclasses
import json
import sys
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, get_config, list_configs
from repro.core.collector import flatten_named, unflatten_named
from repro.launch import steps as steps_mod
from repro.launch.hlo import parse_hlo_collectives
from repro.launch.mesh import make_production_mesh
from repro.models import attention as attn_mod
from repro.models import model as model_mod
from repro.models.model import Model, block_apply, block_init, \
    block_init_cache, build_plan
from repro.optim.adamw import AdamW
from repro.sharding import rules

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def cost_cfg(cfg, seq):
    """Variant whose primitives are scan-free (correct flop counting).
    SSM chunked scans keep the production chunk size but run as an unrolled
    python loop (ssm.UNROLL_SCAN)."""
    from repro.models import ssm as ssm_mod
    ssm_mod.UNROLL_SCAN = True
    return dataclasses.replace(cfg, scan_layers=False)


def _cost(lowered):
    c = lowered.compile()
    ca = c.cost_analysis()
    coll = parse_hlo_collectives(c.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(coll["total"]["operand_bytes"]),
            "coll_ops": int(coll["total"]["count"])}


def _scaled(c, k):
    return {kk: v * k for kk, v in c.items()}


def _add(*cs):
    out = {"flops": 0.0, "bytes": 0.0, "coll": 0.0, "coll_ops": 0.0}
    for c in cs:
        for k in out:
            out[k] += c[k]
    return out


def _shard_params(named_sds, mesh, prefix=""):
    return {n: NamedSharding(mesh, rules.param_pspec(prefix + n, s.shape,
                                                     mesh))
            for n, s in named_sds.items()}


def block_cost(cfg, kind, mesh, B, S, mode, seq_len=None):
    """mode: 'train' | 'fwd' | 'decode'."""
    cfg2 = cost_cfg(cfg, S if mode != "decode" else (seq_len or S))
    psds = jax.eval_shape(
        lambda k: block_init(k, cfg2, kind, jnp.dtype(cfg.param_dtype)),
        jax.random.PRNGKey(0))
    named = flatten_named(psds)
    psh = unflatten_named(_shard_params(named, mesh, "layers.0."), psds)
    bspec = rules.batch_pspec(mesh, B)
    x_sds = jax.ShapeDtypeStruct((B, 1 if mode == "decode" else S,
                                  cfg.d_model), jnp.dtype(cfg.compute_dtype))
    x_sh = NamedSharding(mesh, P(*(list(bspec) + [None, None])))

    if mode == "train":
        def f(p, x):
            def g(p, x):
                out, aux, _ = block_apply(p, cfg2, kind, x, None)
                return (out.astype(jnp.float32) ** 2).sum() * 0.5 + aux
            fn = jax.checkpoint(g) if cfg.remat else g
            return jax.value_and_grad(fn, argnums=(0, 1))(p, x)
        low = jax.jit(f, in_shardings=(psh, x_sh)).lower(psds, x_sds)
    elif mode == "fwd":
        def f(p, x):
            out, aux, _ = block_apply(p, cfg2, kind, x, None)
            return out
        low = jax.jit(f, in_shardings=(psh, x_sh)).lower(psds, x_sds)
    else:
        csds = jax.eval_shape(
            lambda: block_init_cache(cfg2, kind, B, seq_len,
                                     jnp.dtype(cfg.compute_dtype)))
        cnamed = flatten_named(csds)
        csh = unflatten_named(
            {n: NamedSharding(mesh, rules.cache_pspec(
                n, s.shape, mesh, B % 256 == 0, 0))
             for n, s in cnamed.items()}, csds)

        def f(p, c, x):
            out, aux, nc = block_apply(p, cfg2, kind, x, None, cache=c,
                                       pos=jnp.int32(seq_len - 1),
                                       decode=True)
            return out, nc
        low = jax.jit(f, in_shardings=(psh, csh, x_sh)).lower(psds, csds,
                                                              x_sds)
    return _cost(low)


def stem_cost(cfg, mesh, B, S, mode, shape):
    cfg0 = dataclasses.replace(cost_cfg(cfg, S), n_layers=0)
    model0 = Model(cfg0)
    psds = jax.eval_shape(model0.init, jax.random.PRNGKey(0))
    named = flatten_named(psds)
    psh = unflatten_named(_shard_params(named, mesh), psds)
    model_mod.COST_MODE = True
    try:
        if mode == "decode":
            data = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
            bsh = {"tokens": NamedSharding(
                mesh, P(*(list(rules.batch_pspec(mesh, B)) + [None])))}

            def f(p, b):
                h = model0.embed(p, b)
                from repro.models.layers import rmsnorm
                h = rmsnorm(p["final_norm"], h)
                return model0.unembed(p, h)
            low = jax.jit(f, in_shardings=(psh, bsh)).lower(psds, data)
        else:
            data = steps_mod.input_specs(cfg0, shape)
            from repro.launch.dryrun import _batch_shardings
            bsh = _batch_shardings(data, mesh, True)
            if mode == "train":
                def f(p, b):
                    return jax.value_and_grad(
                        lambda pp: model0.loss(pp, b)[0])(p)
            else:
                def f(p, b):
                    h, _ = model0.forward(p, b)
                    return model0.unembed(p, h[:, -1:])
            low = jax.jit(f, in_shardings=(psh, bsh)).lower(psds, data)
        return _cost(low)
    finally:
        model_mod.COST_MODE = False


def opt_cost(cfg, mesh):
    model = Model(cfg)
    psds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    named = flatten_named(psds)
    psh = unflatten_named(_shard_params(named, mesh), psds)
    opt = AdamW(lr=1e-4)
    osds = jax.eval_shape(opt.init, psds)
    onamed = flatten_named(osds)
    osh = unflatten_named(
        {n: NamedSharding(
            mesh, rules.with_data_axis(
                rules.param_pspec(n.split(".", 1)[-1], s.shape, mesh),
                s.shape, mesh, rules.dp_axes(mesh)))
         for n, s in onamed.items()}, osds)
    low = jax.jit(opt.update, in_shardings=(psh, psh, osh)).lower(
        psds, psds, osds)
    return _cost(low)


def active_params(cfg) -> tuple[int, int]:
    model = Model(cfg)
    psds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    named = flatten_named(psds)
    total = active = 0
    for n, s in named.items():
        cnt = int(np.prod(s.shape))
        if "word_embeddings" in n or n == "lm_head":
            continue
        total += cnt
        if ".experts." in n and cfg.moe is not None:
            active += cnt * cfg.moe.top_k // cfg.moe.n_experts
        else:
            active += cnt
    return total, active


def roofline_pair(arch, shape_name):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, reason = cfg.supports_shape(shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skip",
                "reason": reason}
    mesh = make_production_mesh()
    chips = mesh.size
    plan = build_plan(cfg)
    dp_total = int(np.prod([mesh.shape[a] for a in rules.dp_axes(mesh)]))

    if shape.kind == "train":
        n_micro = steps_mod.default_n_micro(cfg, shape, dp_total)
        B_eff = shape.global_batch // n_micro
        mode = "train"
    else:
        n_micro = 1
        B_eff = shape.global_batch
        mode = "fwd" if shape.kind == "prefill" else "decode"

    kinds = {}
    for seg in plan:
        kinds[seg.kind] = kinds.get(seg.kind, 0) + seg.n
    total = _add()
    parts = {}
    batch_sharded = shape.global_batch % dp_total == 0
    with rules.activate(mesh, batch_sharded):
        for kind, count in kinds.items():
            c = block_cost(cfg, kind, mesh, B_eff, shape.seq_len, mode,
                           seq_len=shape.seq_len)
            parts[f"block:{kind}x{count}"] = c
            total = _add(total, _scaled(c, count))
        stem = stem_cost(cfg, mesh, B_eff, shape.seq_len, mode, shape)
        parts["stem"] = stem
        total = _add(total, stem)
        total = _scaled(total, n_micro)
        if mode == "train":
            oc = opt_cost(cfg, mesh)
            parts["opt"] = oc
            total = _add(total, oc)

    terms = {"compute": total["flops"] / PEAK_FLOPS,
             "memory": total["bytes"] / HBM_BW,
             "collective": total["coll"] / ICI_BW}
    dom = max(terms, key=terms.get)
    n_total, n_active = active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        model_flops = 2 * n_active * shape.global_batch
    hlo_flops_global = total["flops"] * chips
    rec = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "chips": chips, "n_micro": n_micro,
        "per_device": total,
        "parts": {k: v for k, v in parts.items()},
        "terms": terms, "dominant": dom,
        "model_flops": model_flops,
        "hlo_flops_global": hlo_flops_global,
        "useful_ratio": (model_flops / hlo_flops_global
                         if hlo_flops_global else 0.0),
        "n_params": n_total, "n_active": n_active,
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", default=None)
    ap.add_argument("--shapes", default=None)
    args = ap.parse_args()
    archs = (args.archs.split(",") if args.archs else
             [a for a in list_configs() if a != "gpt-paper"])
    shapes = args.shapes.split(",") if args.shapes else list(INPUT_SHAPES)
    for arch in archs:
        for shp in shapes:
            try:
                rec = roofline_pair(arch, shp)
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": arch, "shape": shp, "status": "fail",
                       "error": f"{type(e).__name__}: {e}"}
            print(json.dumps(rec))
            sys.stdout.flush()


if __name__ == "__main__":
    main()
