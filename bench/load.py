"""Finds the benchmark's pieces by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration as run, its source,
  what was cut and the reference module (``bench/references/<name>.py``);
* ``bench/traffic/<traffic>.json``: the job's parameters;
* ``bench/workloads/<cell>.json``: the cell's step time, which sizes its
  window, and its correctness limits with the readings they were set
  from;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric;
* ``bench/peaks.json``: the chips' peaks by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT, "BENCHMARK.json")


def cell(name: str) -> dict:
    """The cell's ``BENCHMARK.json`` entry, with ``config`` and
    ``traffic`` replaced by the contents of their files, and the
    workload file's ``limits`` and ``window_step_s``."""
    bj = benchmark()
    entries = {w["name"]: w for w in bj["workloads"]}
    if name not in entries:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(entries)}")
    w = dict(entries[name])
    w["config"] = _json(BENCH, "configs", w["config"] + ".json")
    w["traffic"] = dict(_json(BENCH, "traffic", w["traffic"] + ".json"),
                        name=w["traffic"])
    spec = _json(BENCH, "workloads", name + ".json")
    w["limits"], w["window_step_s"] = spec["limits"], spec["window_step_s"]
    w["end_to_end"] = [m for m in bj["end_to_end"]
                       if name in m.get("workloads", [name])]
    w["per_layer"] = [m for m in bj["per_layer"]
                      if name in m.get("workloads", [name])]
    return w


def override(cell: dict, overrides) -> dict:
    """``cell`` with parts replaced (tests): ``overrides`` maps ``config``
    (keys of the configuration as run), ``traffic`` or ``limits`` to the
    values that replace theirs."""
    for part, vals in (overrides or {}).items():
        if part == "config":
            cell["config"] = dict(cell["config"],
                                  config=dict(cell["config"]["config"], **vals))
        else:
            cell[part] = dict(cell[part], **vals)
    return cell


def peaks(device_kind: str) -> dict:
    table = _json(BENCH, "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def _module(kind: str, name: str):
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no {kind[:-1]} {name!r}: {path} is missing")
    mod_name = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(name: str):
    return _module("references", name)


def metric(name: str):
    """The reader of a per-layer metric: ``read(ctx) -> float | None``."""
    return _module("metrics", name)
