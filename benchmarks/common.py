"""Benchmark harness utilities: timing + the ``name,us_per_call,derived``
CSV contract used by benchmarks.run."""
from __future__ import annotations

import os
import subprocess
import sys
import time

ROWS: list[tuple] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}")


def write_json(path: str, rows=None):
    """Persist emitted rows as {name: us_per_call} (BENCH_*.json contract).

    A no-op under ``REPRO_BENCH_SMOKE`` (benchmarks.run --smoke): smoke
    runs exercise every bench but must never overwrite tracked rows with
    tiny-step numbers — enforced here so EVERY bench honors it."""
    import json
    if os.environ.get("REPRO_BENCH_SMOKE"):
        return
    with open(path, "w") as f:
        json.dump({name: us for name, us, _ in (rows or ROWS)}, f,
                  indent=2, sort_keys=True)


def timeit(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    """Median wall time per call in microseconds."""
    for _ in range(warmup):
        fn(*args)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def cpu_host_devices(env, devices: int) -> None:
    """Give a CPU run ``devices`` virtual host devices.  Only under
    ``JAX_PLATFORMS=cpu``: on an accelerator a worker uses the real
    devices."""
    if env.get("JAX_PLATFORMS") == "cpu":
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"


def run_worker(module: str, *args, devices: int = 8, timeout: int = 1800,
               cpu: bool = False) -> str:
    """Run a benchmark worker in a subprocess, the one process that touches
    JAX (a parent holding an accelerator would lock the worker out).  Under
    ``JAX_PLATFORMS=cpu`` — or with ``cpu=True``, for workers that simulate
    a mesh — it gets ``devices`` forced host devices."""
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    cpu_host_devices(env, devices)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-m", module, *map(str, args)],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if out.returncode != 0:
        raise RuntimeError(f"{module} failed:\n{out.stdout}\n{out.stderr}")
    return out.stdout


def run_bench(module: str, timeout: int = 7200) -> None:
    """Run ``benchmarks.<module>.run()`` in a child process, echo its
    output, and collect the rows it emitted (for ``--json``)."""
    out = run_worker(f"benchmarks.{module}", timeout=timeout, devices=1)
    for line in out.splitlines():
        print(line)
        parts = line.split(",", 2)
        if len(parts) == 3:
            try:
                ROWS.append((parts[0], float(parts[1]), parts[2]))
            except ValueError:
                pass
