"""BENCHMARK.json against chipbench's files: every metric has a reader
that agrees with its entry, names keep to the allowed characters, every
configuration keeps to its own source, and a cell, configuration, mix,
metric or architecture is added by files alone."""

import copy
import json
import os
import re
import shutil
import subprocess
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
    # the configuration against its OWN source: every published key as
    # published unless reduced, and no width reduced
    assert cells.published_faults(cfg) == []
    assert os.path.exists(os.path.join(ROOT, "chipbench", "archs",
                                       cfg["arch"] + ".py"))
    if "facebook/opt-350m" in entry["source"]:
        # no width differs from facebook/opt-350m's config.json
        assert (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["ffn_dim"], cfg["vocab_size"],
                cfg["max_position_embeddings"]) == (1024, 16, 4096,
                                                    50272, 2048)
        assert cfg["num_hidden_layers"] == 24 or \
            "num_hidden_layers" in cfg["reduced"]


OLMOE = {   # the catalog row's ``config``, as a source gives its keys
    "hidden_size": 2048, "intermediate_size": 1024,
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "vocab_size": 50304, "max_position_embeddings": 4096,
    "rope_scaling": {"factor": 2.0, "head_dim": 128},
    "norm_topk_prob": False, "hidden_act": "silu"}


def _config(reduced=(), **changed):
    return {"arch": "x", "published": copy.deepcopy(OLMOE),
            **copy.deepcopy(OLMOE), **changed, "reduced": list(reduced)}


@pytest.mark.parametrize("cfg, fault", [
    (_config(), None),
    # a cut depth, a chip's share of the experts and of the vocabulary
    (_config(["num_hidden_layers"], num_hidden_layers=1), None),
    (_config(["num_experts", "vocab_size"], num_experts=16,
             vocab_size=12576), None),
    # a width changed, listed or not
    (_config(intermediate_size=512), "intermediate_size is 512"),
    (_config(["intermediate_size"], intermediate_size=512),
     "intermediate_size is a width"),
    (_config(["hidden_size"]), "hidden_size is a width"),
    (_config(["num_experts_per_tok"], num_experts_per_tok=2),
     "num_experts_per_tok is a width"),
    # a depth cut that is not listed, a count that grew, a key left out
    (_config(num_hidden_layers=1), "num_hidden_layers is 1"),
    (_config(["num_hidden_layers"], num_hidden_layers=32), "no cut"),
    (_config(["vocab_size"], vocab_size="all"), "no cut"),
    ({k: v for k, v in _config().items() if k != "hidden_act"},
     "hidden_act is published and missing"),
    (_config(["n_layer"]), "n_layer is reduced and not published"),
    # a listed group may change, but no width inside it
    (_config(["rope_scaling"], rope_scaling={"factor": 1.0,
                                             "head_dim": 128}), None),
    (_config(["rope_scaling"], rope_scaling={"factor": 2.0,
                                             "head_dim": 64}),
     "rope_scaling.head_dim is a width"),
    # no published block, no reduced list
    ({"arch": "x", "hidden_size": 8, "reduced": []}, '"published"'),
    ({"arch": "x", "published": {}}, '"reduced"'),
], ids=lambda v: None if isinstance(v, dict) else str(v))
def test_published_rule(cfg, fault):
    faults = cells.published_faults(cfg)
    if fault is None:
        assert faults == []
    else:
        assert len(faults) == 1 and fault in faults[0]


@pytest.mark.parametrize("break_it, says", [
    (lambda cfg: cfg.pop("arch"), "names no architecture"),
    (lambda cfg: cfg.update(arch="no_such"), "no architecture 'no_such'"),
    (lambda cfg: cfg.update(ffn_dim=2048), "departs from its source"),
], ids=["no-arch", "unknown-arch", "changed-width"])
def test_a_configuration_out_of_line_is_refused_by_name(tmp_path, break_it,
                                                        says):
    """No OPT by default: the error names the configuration's file."""
    cfg = cells.load_json(os.path.join(
        ROOT, "chipbench", "configs", "opt-350m-train.json"))
    break_it(cfg)
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"][0]["file"] = "cfg.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SystemExit) as e:
        cell = cells.load_cell(str(tmp_path), "opt350m_train")
        cells.load_arch(cell["config_file"]["arch"])
    assert says in str(e.value)
    if "architecture '" not in says:
        assert "cfg.json" in str(e.value)


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
    # ``setup_enter_s`` names no cells, so every cell reports it
    assert [m["name"] for m in cell["per_layer"]] == ["setup_enter_s",
                                                      "requests_done"]
    run = {"setup_s": 1.5, "setup_phases": {"entered": 0.75}, "requests": [
        {"done": True, "due": 0.0, "first": 0.25, "retire": 1.25,
         "tokens": 11},
        {"done": False, "due": 1.0}]}
    got = cells.read_metrics(cell, "per_layer", run, str(here))
    assert got == {"requests_done": {"value": 1.0, "unit": "1"},
                   "setup_enter_s": {"value": 0.75, "unit": "s"}}
    e2e = cells.read_metrics(cell, "end_to_end", run, str(here))
    assert e2e["itl_mean_ms"]["value"] == pytest.approx(100.0)
    assert e2e["ttft_p90_ms"]["value"] == pytest.approx(250.0)
    assert e2e["setup_s"]["value"] == 1.5
    assert all(p.read_bytes() == data for p, data in before.items())


def test_an_architecture_is_added_by_files_alone(tmp_path):
    """A second architecture (``twin_arch.py``: no attention, no
    position table, a configuration with other keys than OPT's), its
    configuration, a mix and a cell: files dropped beside the old ones
    and entries appended. No file that was there is edited, the cell
    rehearses ``correct`` on the CPU, and the arithmetic answers from
    the new architecture's own formulas."""
    here = tmp_path / "chipbench"
    shutil.copytree(os.path.join(ROOT, "chipbench"), here,
                    ignore=shutil.ignore_patterns(".cache",
                                                  "__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    shutil.copy(os.path.join(ROOT, "tests", "chipbench", "twin_arch.py"),
                here / "archs" / "ffnlm.py")
    sizes = {"hidden_size": 64, "intermediate_size": 256,
             "num_hidden_layers": 4, "vocab_size": 512}
    cfg = {"source": "tests/chipbench/twin_arch.py", "arch": "ffnlm",
           "published": sizes, **sizes, "num_hidden_layers": 2,
           "reduced": ["num_hidden_layers"]}
    assert not set(cfg) & {"ffn_dim", "num_attention_heads"}
    (here / "configs" / "ffnlm-2L.json").write_text(json.dumps(cfg))
    mix = {"driver": "train_steps", "seq_len": 64, "batch": 2,
           "n_batches": 2, "warmup_steps": 2, "trace_steps": 2,
           "check_rows": 16}
    (here / "traffic" / "pretrain_short.json").write_text(json.dumps(mix))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "ffnlm-2L", "source": "x", "why": "x",
         "file": "chipbench/configs/ffnlm-2L.json",
         "reduced": ["num_hidden_layers"]})
    bench["workloads"].append(
        {"name": "ffnlm_train", "config": "ffnlm-2L", "chips": 1,
         "traffic": "pretrain_short", "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("tokens_per_s", "train_mfu_pct"):
            m["workloads"] = m["workloads"] + ["ffnlm_train"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path), ROOT]))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "ffnlm_train",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0",
         "--rehearse"], cwd=tmp_path, env=env, text=True,
        capture_output=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    # the program declares no ``pos`` and was fed none; the tolerances
    # printed are the twin's own
    assert "tolerance 2e-02" in p.stdout and "tolerance 2e-04" in p.stdout

    # the copy's arith, in a process that imports the copy
    p = subprocess.run(
        [sys.executable, "-c",
         "import json; from chipbench import arith, cells\n"
         "assert cells.HERE.startswith(%r)\n"
         "cfg = cells.load_cell(%r, 'ffnlm_train')['config_file']\n"
         "print(json.dumps([arith.train_flops_per_token(cfg, 64),"
         " arith.flash_flops_per_step(cfg, 2, 64),"
         " arith.decode_step_bytes(cfg, 2, 10, 3),"
         " arith.matmul_scopes(cfg)]))"
         % (str(tmp_path), str(tmp_path))],
        cwd=tmp_path, env=env, text=True, capture_output=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    d, f, v = 64, 256, 512
    assert json.loads(p.stdout.strip().splitlines()[-1]) == [
        3 * (2 * 4 * d * f + 2 * d * v), 0,
        2 * (2 * 2 * d * f + d * v + 3 * d), ["mul"]]
    assert all(p.read_bytes() == data for p, data in before.items())
