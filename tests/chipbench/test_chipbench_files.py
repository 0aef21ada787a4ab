"""BENCHMARK.json against chipbench's files: every metric has a reader
that agrees with its entry, names keep to the allowed characters, and a
cell, configuration, mix or metric is added by files alone."""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import cells  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cells_of(metric):
    return metric.get("workloads", CELLS)


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"],
    ids=lambda m: m["name"])
def test_metric_file_agrees_with_its_entry(metric):
    mod = cells.load_metric(metric["name"])
    assert mod.UNIT == metric["unit"] and UNIT.match(metric["unit"])
    assert mod.SOURCE == metric["source"]
    assert NAME.match(metric["name"])
    assert metric["better"] in ("lower", "higher")
    assert callable(mod.read) and (mod.__doc__ or "").strip()
    if metric["name"] in E2E:
        assert mod.LAYER is None and mod.MOVES is None
        assert mod.SOURCE in ("host_clock", "device_trace")
        assert 0 < metric["bound"] <= 0.1
        return
    assert mod.LAYER == metric["layer"] and mod.MOVES == metric["moves"]
    moved = E2E[metric["moves"]]
    for cell in _cells_of(metric):      # the moved metric is reported
        assert cell in _cells_of(moved)  # wherever this one is


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_files(cell):
    c = cells.load_cell(ROOT, cell)
    assert NAME.match(c["name"]) and NAME.match(c["traffic"])
    assert c["chips"] in (1, 4) and 1 <= len(c["why"]) <= 200
    names = [m["name"] for m in c["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2 and c["per_layer"]
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "drivers",
        c["traffic_file"]["driver"] + ".py"))
    cfg = c["config_file"]
    (entry,) = [e for e in BENCH["configs"] if e["name"] == c["config"]]
    assert entry["reduced"] == cfg["reduced"]
    # no width differs from facebook/opt-350m's config.json
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["ffn_dim"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (1024, 16, 4096, 50272,
                                                2048)
    assert cfg["num_hidden_layers"] == 24 or \
        "num_hidden_layers" in cfg["reduced"]


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    assert E2E["setup_s"]["bound"] <= 0.1
    for path in BENCH["paths"]:
        for base, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base or "/." in base[len(ROOT):]:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (base, f)
    layers = {m["layer"] for m in BENCH["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(("| " + layer + " |") in perf for layer in layers)


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new configuration, mix, metric and cell: files dropped beside
    the old ones and entries appended to BENCHMARK.json. No file that
    was there is edited, and the harness finds all four by name."""
    here = tmp_path / "chipbench"
    shutil.copytree(os.path.join(ROOT, "chipbench"), here,
                    ignore=shutil.ignore_patterns(".cache",
                                                  "__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    cfg = cells.load_json(os.path.join(
        ROOT, "chipbench", "configs", "opt-350m-serve-8L.json"))
    cfg["slots"] = 8
    (here / "configs" / "opt-350m-serve-8L-s8.json").write_text(
        json.dumps(cfg))
    mix = cells.load_json(os.path.join(ROOT, "chipbench", "traffic",
                                       "chat.json"))
    mix["rate_per_s"] = 1.0
    (here / "traffic" / "chat_slow.json").write_text(json.dumps(mix))
    (here / "metrics" / "requests_done.py").write_text(
        '"""Requests of the window that finished."""\n'
        'UNIT, SOURCE = "1", "program_counter"\n'
        'LAYER, MOVES = "serving engine", "itl_mean_ms"\n\n\n'
        'def read(run):\n'
        '    return sum(r["done"] for r in run["requests"])\n')
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "opt-350m-serve-8L-s8", "source": "x", "why": "x",
         "file": "chipbench/configs/opt-350m-serve-8L-s8.json",
         "reduced": ["num_hidden_layers"]})
    bench["workloads"].append(
        {"name": "chat_slow_s8", "config": "opt-350m-serve-8L-s8",
         "traffic": "chat_slow", "chips": 1, "why": "x"})
    bench["per_layer"].append(
        {"name": "requests_done", "unit": "1", "better": "higher",
         "source": "program_counter", "layer": "serving engine",
         "moves": "itl_mean_ms", "workloads": ["chat_slow_s8"]})
    bench["end_to_end"] += [          # the serving metrics' readers
        {"name": name, "unit": "ms", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": ["chat_slow_s8"]}
        for name in ("ttft_p90_ms", "itl_mean_ms")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell(str(tmp_path), "chat_slow_s8", str(here))
    assert cell["config_file"]["slots"] == 8
    assert cell["traffic_file"]["rate_per_s"] == 1.0
    assert [m["name"] for m in cell["per_layer"]] == ["requests_done"]
    run = {"setup_s": 1.5, "requests": [
        {"done": True, "due": 0.0, "first": 0.25, "retire": 1.25,
         "tokens": 11},
        {"done": False, "due": 1.0}]}
    got = cells.read_metrics(cell, "per_layer", run, str(here))
    assert got == {"requests_done": {"value": 1.0, "unit": "1"}}
    e2e = cells.read_metrics(cell, "end_to_end", run, str(here))
    assert e2e["itl_mean_ms"]["value"] == pytest.approx(100.0)
    assert e2e["ttft_p90_ms"]["value"] == pytest.approx(250.0)
    assert e2e["setup_s"]["value"] == 1.5
    assert all(p.read_bytes() == data for p, data in before.items())
