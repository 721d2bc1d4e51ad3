"""Smoke run of the supervised TTrace loop on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # only the paths that span chips

It drives ``repro.launch.supervise.main``, the entry point a user calls, at
the published widths of TinyLlama-1.1B (d_model 2048, 32 heads and 4 KV
heads, d_ff 5632, vocab 32000) with random weights from a seed; only the
depth (and, on four chips, the sequence) is cut to fit.  TinyLlama is used because it is the
CLI's default architecture.  Before that it checks the packed rel-err
kernel, compiled, against its XLA oracle.

One chip:
  * the packed kernel on ragged sections at trace widths;
  * (a) a clean dense run (float32 reference and candidate) must PASS;
  * (b) fp8-tile128 with the stale-scale bug must be flagged, bisected and
    localized to ``layers.*.mlp``;
  * (c) a clean fp8-tile128 run must PASS under bfloat16 thresholds.
Four chips:
  * the shard_map candidate at dp=2 x tp=2, clean, and with the missing
    row-parallel all-reduce (flagged, localized to ``layers.*.mlp``);
  * the 1F1B pipeline over four stages and four microbatches, clean.

Any watchdog or LOUD event, any threshold-estimation or merge-plan
fallback, and any wrong verdict fails the run.  It exits non-zero and
prints no result when JAX finds no TPU or the repository's sources are
missing.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import fnmatch
import json
import os
import shutil
import sys
import time
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chip_smoke_out")

ARCH = ["--arch", "tinyllama-1.1b", "--seed", "0", "--check-every", "1",
        "--no-spill"]
# One chip holds both sides: float32 parameters, Adam's master copy and
# moments per side, the initial states (checkpoint templates), the last
# step's trace pair in the ring and the current one — about 26 copies of
# the parameters.  At 1 layer plus the tied embedding (110M parameters,
# 0.44 GB a copy) that is 11.5 GB before activations; 2 layers (0.61 GB a
# copy) do not fit 16 GB.  Checks run synchronously so the ring keeps one
# pair; one checkpoint at step 0 serves bisection.
ONE_CHIP = ARCH + ["--layers", "1", "--batch", "1", "--seq", "2048",
                   "--steps", "4", "--ckpt-every", "4",
                   "--async-window", "0", "--ring-window", "1"]
# Across chips the reference (one device, whole model) shares the last
# chip with a candidate shard, and four microbatches need a batch of four:
# sequences of 1024 tokens; one layer for the dp x tp mesh, two for the
# pipeline (four stages, two of them pass-through).
MESH = ARCH + ["--layers", "1", "--batch", "2", "--seq", "1024",
               "--steps", "4", "--ckpt-every", "4",
               "--async-window", "0", "--ring-window", "1"]
PIPE = ARCH + ["--layers", "2", "--batch", "4", "--seq", "1024",
               "--steps", "4", "--ckpt-every", "4",
               "--async-window", "0", "--ring-window", "1"]

RUNS_ONE = [
    ("a_dense_clean", ONE_CHIP + ["--recipe", "dense", "--dp", "1",
                                  "--tp", "1"], None),
    ("b_fp8_stale_scale", ONE_CHIP + ["--recipe", "fp8-tile128", "--bug",
                                      "fp8_stale_scale"], "layers.*.mlp"),
    ("c_fp8_clean", ONE_CHIP + ["--recipe", "fp8-tile128"], None),
]
RUNS_FOUR = [
    ("dp2tp2_clean", MESH + ["--recipe", "dense", "--dp", "2", "--tp", "2"],
     None),
    ("dp2tp2_missing_row_psum", MESH + ["--recipe", "dense", "--dp", "2",
                                        "--tp", "2", "--bug",
                                        "tp_missing_row_psum"],
     "layers.*.mlp"),
    ("pp1f1b4x4_clean", PIPE + ["--recipe", "pp-1f1b", "--pp", "4",
                                "--microbatches", "4"], None),
]


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileClock:
    """Seconds spent compiling (or fetching from the persistent cache) and
    persistent-cache hits, from JAX's monitoring events."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0

        def on_duration(name, secs, **_):
            if name == self.COMPILE_EVENT:
                self.seconds += secs

        def on_event(name, **_):
            if name == self.HIT_EVENT:
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.seconds, self.hits


def peak_bytes(devices) -> list[int]:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices]


def packed_section(sizes, key, block):
    """Random pairs already in the kernel's packed layout (what
    ``relerr_engine.pack_device`` builds), made on the device in one go:
    each pair starts on a block boundary, its ragged tail is zero-filled,
    and the middle pair's reference is all zeros."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    nblocks = [-(-n // block) for n in sizes]
    seg = np.repeat(np.arange(len(sizes), dtype=np.int32), nblocks)
    cnt = np.concatenate([np.clip(n - np.arange(nb) * block, 0, block)
                          for n, nb in zip(sizes, nblocks)]).astype(np.int32)
    valid = jnp.arange(block)[None, :] < jnp.asarray(cnt)[:, None]
    zero = jnp.asarray(seg == len(sizes) // 2)[:, None]
    k1, k2 = jax.random.split(key)
    a = jax.random.normal(k1, valid.shape, jnp.float32)
    a = jnp.where(valid & ~zero, a, 0.0)
    b = jnp.where(valid, a + 1e-3 * jax.random.normal(k2, valid.shape), 0.0)
    return (a.reshape(-1), b.reshape(-1), jnp.asarray(seg),
            jnp.asarray(cnt))


def check_kernel() -> str:
    """The compiled packed kernel against its XLA oracle on ragged
    sections at trace widths."""
    import jax
    import numpy as np
    from repro.kernels import relerr as K

    sections = {
        # one TinyLlama layer's parameter tensors (QKV, out-proj, MLP, norms)
        "layer_params": [2048 * 2560, 2048 * 2048, 2048 * 5632, 2048 * 5632,
                         5632 * 2048, 2048, 2048],
        # 200 ragged pairs, up to one KV projection's size
        "ragged_200": [int(n) for n in np.random.default_rng(0).integers(
            1, 2048 * 256, 200)],
        # the 2-layer parameter section (embedding, 2 layers, final norm):
        # more blocks than one launch's SMEM holds metadata for
        "param_section": [32000 * 2048] + 2 * [
            2048 * 2560, 2048 * 2048, 2048 * 5632, 2048 * 5632,
            5632 * 2048, 2048, 2048] + [2048],
    }
    lines = []
    for i, (name, sizes) in enumerate(sections.items()):
        a, b, seg, cnt = packed_section(sizes, jax.random.PRNGKey(i),
                                        K.DEFAULT_BLOCK)
        lowered = K.packed_sq_norms.lower(a, b, seg, cnt,
                                          n_segments=len(sizes),
                                          interpret=False)
        if "tpu_custom_call" not in lowered.compile().as_text():
            fail(f"kernel[{name}]: no Mosaic kernel in the compiled program")
        got = np.asarray(K.packed_sq_norms(a, b, seg, cnt,
                                           n_segments=len(sizes),
                                           interpret=False), np.float64)
        want = np.asarray(K.packed_sq_norms_xla(a, b, seg, len(sizes)),
                          np.float64)
        if got.shape != (len(sizes), 2) or not np.all(np.isfinite(got)):
            fail(f"kernel[{name}]: bad output {got.shape}")
        rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-30)
        worst = float(rel.max())
        if worst > 1e-4:
            fail(f"kernel[{name}]: compiled kernel disagrees with the XLA "
                 f"oracle (worst relative difference {worst:.3e})")
        lines.append(f"kernel[{name}]: {len(sizes)} pairs, {a.size} packed "
                     f"elements, compiled Mosaic kernel matches "
                     f"packed_sq_norms_xla (worst relative difference "
                     f"{worst:.3e})")
    return "\n".join(lines)


def supervised_run(name, argv, expect_module, clock, devices) -> str:
    from repro.launch.supervise import main as supervise_main

    work = os.path.join(OUT_DIR, name)
    shutil.rmtree(work, ignore_errors=True)
    c0, h0 = clock.mark()
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = supervise_main(argv + ["--work-dir", work])
    wall = time.perf_counter() - t0
    c1, h1 = clock.mark()
    shutil.rmtree(work, ignore_errors=True)     # checkpoints are GBs

    fallbacks = [str(w.message) for w in caught
                 if "fallback" in str(w.message).lower()
                 or "falling back" in str(w.message).lower()]
    events = (res.watchdog_events + res.degradations
              + [f"loud step {s}" for s in res.loud_steps])
    if res.checks_rescued or res.checks_lost:
        events.append(f"{res.checks_rescued} checks rescued, "
                      f"{res.checks_lost} lost")
    verdict = "FAIL" if res.flagged else "PASS"
    loc = res.localized_module
    steady = res.timings.get("steady_steps_per_s")
    line = (f"run[{name}]: verdict={verdict} "
            f"first_flagged_step={res.first_flagged_step} "
            f"first_bad_step={res.first_bad_step} localized={loc} "
            f"peak_bytes_in_use={max(peak_bytes(devices))} "
            f"compile_s={c1 - c0:.1f} cache_hits={h1 - h0} "
            f"steady_s_per_step="
            f"{'n/a' if not steady else f'{1.0 / steady:.3f}'} "
            f"wall_s={wall:.1f}")
    print(line, flush=True)
    if events:
        fail(f"{name}: watchdog/loud events {events}")
    if fallbacks:
        fail(f"{name}: fallback warnings {fallbacks}")
    if expect_module is None:
        if res.flagged:
            fail(f"{name}: a clean run was flagged at step "
                 f"{res.first_flagged_step}")
    else:
        if not res.flagged:
            fail(f"{name}: the injected bug went undetected")
        if res.first_bad_step is None or res.bisection is None:
            fail(f"{name}: the flag was not bisected")
        if loc is None or not fnmatch.fnmatchcase(loc, expect_module):
            fail(f"{name}: localized to {loc}, expected {expect_module} "
                 f"(MISMATCH)")
        line += f" [MATCH {expect_module}]"
    return line


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span four chips: the "
                         "dp=2 x tp=2 mesh and the 4-stage 1F1B pipeline")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"no TPU: JAX found platform {platform!r} "
             f"({len(devices)} device(s))")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        fail(f"need {want} TPU chips, found {len(devices)}")
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    os.makedirs(OUT_DIR, exist_ok=True)
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
          f"compile cache {cache_dir}", flush=True)

    lines = []
    if args.four_chips:
        runs = RUNS_FOUR
    else:
        t0 = time.perf_counter()
        lines.append(check_kernel())
        print(lines[-1] + f" ({time.perf_counter() - t0:.1f}s)", flush=True)
        runs = RUNS_ONE
    for name, argv, expect in runs:
        lines.append(supervised_run(name, argv, expect, clock, devices))
    if args.four_chips:
        # a mesh that silently collapsed onto device 0 leaves the others idle
        peaks = peak_bytes(devices)
        lines.append(f"per-chip peak_bytes_in_use={peaks}")
        print(lines[-1], flush=True)
        if min(peaks) == 0:
            fail(f"a chip was never used: peaks {peaks}")
    with open(os.path.join(OUT_DIR, "summary.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
