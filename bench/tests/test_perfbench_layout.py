"""The benchmark's files are found by name, ``BENCHMARK.json`` keeps to
its layout, and the harness refuses what it cannot measure."""
import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import load  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BJ = load.benchmark()
WIDTHS = {"hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "num_experts_per_tok", "moe_intermediate_size"}


def test_top_level_keys():
    assert set(BJ) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BJ["paths"] == ["bench"]
    assert BJ["command"] == ["python3", "bench/run.py"]
    assert 1 <= BJ["run_seconds"] <= 51


@pytest.mark.parametrize("cfg", BJ["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(cfg):
    assert NAME.match(cfg["name"])
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    # every key changed from the published config is listed, with its
    # published value, and none is a width
    assert sorted(body["reduced"]) == sorted(cfg["reduced"])
    for k in cfg["reduced"]:
        assert NAME.match(k)
        assert not k.endswith(("_dim", "_rank"))
        assert k not in WIDTHS
        assert body["config"][k] != body["reduced"][k]
    ref = load.reference(body["reference"])
    assert callable(ref.loss) and callable(ref.train_step)
    assert callable(ref.train_flops)


@pytest.mark.parametrize("w", BJ["workloads"], ids=lambda w: w["name"])
def test_each_cell_loads_by_name(w):
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    cell = load.cell(w["name"])
    assert cell["config"]["name"] == w["config"]
    assert cell["traffic"]["name"] == w["traffic"]
    need = {"loss_gap", "grad_gap", "change_gap", "window_loss_gap",
            "verdict_events", "window_compiles"}
    assert need <= set(cell["limits"])
    assert cell["window_step_s"] > 0
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"]


def test_cells_are_distinct_pairs():
    pairs = [(w["config"], w["traffic"]) for w in BJ["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in BJ["workloads"]}
    assert used == {c["name"] for c in BJ["configs"]}


@pytest.mark.parametrize("m", BJ["end_to_end"] + BJ["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    else:
        e2e = {x["name"] for x in BJ["end_to_end"]}
        assert m["moves"] in e2e
        reader = load.metric(m["name"])
        assert callable(reader.read)


def test_unknown_device_kind_is_refused():
    assert load.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        load.peaks("TPU v9 imaginary")


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        load.cell("no-such-cell")
    with pytest.raises(KeyError):
        load.metric("no_such_metric")


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         BJ["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


TRAFFIC = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))


@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_files_hold_a_whole_job(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        t = json.load(f)
    assert NAME.match(name)
    assert t["batch"] >= 1 and t["seq"] >= 1 and t["recipe"] == "dense"
    assert {"check_every", "async_window", "ring_window", "spill", "journal",
            "ckpt_every", "ckpt_keep"} <= set(t["supervise"])
    assert {"lr", "b1", "b2", "eps", "weight_decay", "clip"} == \
        set(t["optimizer"])
