"""The cell ``smallthinker_train_T16k`` (ISSUE 46): its files hold to
their source, the cell rehearses on the CPU through ``run.py``, each
planted dropped term fails ``correct`` through the driver, the
arithmetic is the issue's, and each new reader reads a small window
written out by hand."""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import arith, cells, peaks, spans, tracing   # noqa: E402
from chipbench.drivers import train_steps                   # noqa: E402

CELL = "smallthinker_train_T16k"
DEV = "/device:TPU:0"
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
NEW = ("moe_route_dev_share_pct", "expert_gate_active_pct")


def _tiny_cell():
    """The cell cut to its rehearsal size, as ``run.load_cell`` cuts it
    (without steering the kernels: the dense path on the CPU)."""
    cell = cells.load_cell(ROOT, CELL)
    for part in ("config_file", "traffic_file"):
        cell[part] = {**cell[part], **cell[part].get("rehearse", {})}
    return cell


def test_the_configuration_holds_to_its_source():
    cell = cells.load_cell(ROOT, CELL)
    cfg = cell["config_file"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = []
    if os.path.exists(catalog):         # the guides' catalog, where it is
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if line.strip()]
    for row in rows:
        if row["name"] == "SmallThinker-21BA3B-Instruct":
            assert cfg["published"] == row["config"]
            assert cfg["source"] == row["source_url"]
    assert cells.published_faults(cfg) == []
    assert cfg["reduced"] == REDUCED
    assert [cfg[key] for key in REDUCED] == [4, 16, 37984]
    # every width as published, both layout lists whole
    for key, value in cfg["published"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_ffn_hidden_size"], cfg["sliding_window_size"],
            cfg["moe_num_active_primary_experts"], cfg["rope_theta"],
            cfg["published"]["moe_num_primary_experts"]) == (
                2560, 28, 4, 128, 768, 4096, 6, 1500000, 64)
    for layout in ("sliding_window_layout", "rope_layout"):
        assert cfg[layout] == [0, 1, 1, 1] * 13
        assert cells.is_width("sliding_window_layout")     # so not reduced
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    assert (cfg["first_expert"], cfg["num_experts"]) == (0, 16)
    assert "4 chips" in cfg["deployment"] and "52 chips" in cfg["deployment"]
    for said in ("deployment", "parameters", "train_dtype"):
        assert cfg[said]
    assert set(cfg["assumed"]) >= {
        "router_input", "rope", "auxiliary_loss", "num_experts",
        "first_expert", "embedding_init_std", "router_init_std", "seq_len"}
    tiny = {**cfg, **cfg["rehearse"]}
    assert tiny["sliding_window_size"] \
        < cell["traffic_file"]["rehearse"]["seq_len"]
    assert tiny["num_attention_heads"] \
        == 7 * tiny["num_key_value_heads"]                 # a group of 7
    mix = cell["traffic_file"]
    assert (mix["batch"], mix["seq_len"], mix["n_batches"],
            mix["check_rows"]) == (1, 16384, 4, 64)
    assert cell["chips"] == 1 and cell["traffic"] == "pretrain_T16k_b1"
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {
        "flash_roof_pct", "train_mfu_pct", "expert_matmul_roof_pct",
        "moe_glue_dev_share_pct", "expert_rows_max_over_mean",
        "device_idle_pct.train", "setup_enter_s",
        "step_interval_ms.train"} <= names
    assert not {"matmul_roof_pct", "gate_norm_dev_share_pct",
                "norm_rope_dev_share_pct", "hc_dev_share_pct"} & names
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    (route, gate) = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert route["workloads"] == gate["workloads"] == [CELL]
    assert (route["layer"], route["source"], route["better"]) == (
        "kernels", "device_trace", "lower")
    assert (gate["layer"], gate["source"]) == ("train executor",
                                               "program_counter")
    assert bench["per_layer"][-2:] == [route, gate]
    assert bench["workloads"][-1]["name"] == CELL


def test_arithmetic_is_the_issues():
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    arch = cells.load_arch("smallthinker")
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert round(attention / 1e4) == 2097           # 20.97 M
    expert = 3 * 2560 * 768
    assert round(expert / 1e4) == 590               # 5.90 M
    touched = 4 * (attention + 2560 * 64 + 1.5 * expert) + 2560 * 37984
    assert arch.touched_parameters(cfg) == touched
    assert arith.train_flops_per_token(cfg, 0) == 6 * touched
    # what this chip holds: 656.5 M parameters, 10.50 GB at 16 bytes
    held = 4 * (attention + 2560 * 64 + 16 * expert + 2 * 2560) \
        + 2 * 2560 * 37984 + 2560
    assert round(held / 1e5) == 6565 and round(held * 16 / 1e7) == 1050
    t, w = 16384, 4096
    causal, band = t * (t + 1) // 2, w * (w + 1) // 2 + (t - w) * w
    assert arch.useful_scores(t) == causal
    assert arch.useful_scores(t, w) == band
    assert arch.useful_scores(64, 16) == sum(min(i + 1, 16)
                                             for i in range(64))
    assert round(band / causal, 4) == 0.4375
    per_score = 14 * 128
    assert arith.flash_flops_per_step(cfg, 1, t) \
        == per_score * 28 * (causal + 3 * band)
    assert arch.window_flash_flops_per_step(cfg, 1, t) \
        == per_score * 28 * 3 * band
    assert arith.train_flops_per_token(cfg, t) == pytest.approx(
        6 * touched + arith.flash_flops_per_step(cfg, 1, t) / t)
    # the issue's reckoning: some 37 TFLOP a step: flash 15.6 (the one
    # full layer 6.7, the three window layers 2.95 each), projections
    # 8.3, the head 9.6, held experts 3.5, the router 0.06
    step = arith.train_flops_per_token(cfg, t) * t
    assert [round(x / 1e10) for x in (
        step, arith.flash_flops_per_step(cfg, 1, t),
        per_score * 28 * causal, per_score * 28 * band,
        6 * 4 * attention * t, 6 * 2560 * 37984 * t,
        6 * 4 * 1.5 * expert * t, 6 * 4 * 2560 * 64 * t)] == [
            3692, 1557, 673, 295, 825, 956, 348, 6]
    assert arch.expert_flops_per_pair(cfg) == 18 * 2560 * 768
    assert arith.matmul_scopes(cfg) == ("mul",)


@pytest.mark.parametrize("seed", ["3000000019", "2200000013"])
def test_the_cell_rehearses_through_run_py(seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         seed, "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "the reference handed the program's choices" in p.stdout


def _heads_read_j_mod(sound, hkv):
    """`ops.causal_attention.causal_attention` with query head j reading
    key/value head ``j % hkv``: the query heads put in that order, and
    the output's heads put back."""
    import jax.numpy as jnp

    def wrong(q, k, v, n_head, n_kv_head, *rest, **kw):
        order = jnp.asarray(sorted(range(n_head),
                                   key=lambda j: (j % n_kv_head, j)))
        heads = lambda x: x.reshape(x.shape[:2] + (n_head, -1))
        out = sound(heads(q)[:, :, order].reshape(q.shape), k, v, n_head,
                    n_kv_head, *rest, **kw)
        return heads(out)[:, :, jnp.argsort(order)].reshape(q.shape)
    assert hkv > 1
    return wrong


@pytest.mark.parametrize("fault", [
    "sound", "the_router_reads_the_normed_stream", "silu_for_relu",
    "rope_on_the_full_layer_too", "no_rotation_on_a_window_layer",
    "no_window_bound", "the_six_weights_not_normalised",
    "query_head_j_reads_key_head_j_mod_4"])
def test_a_planted_dropped_term_fails_correct(monkeypatch, fault):
    """The whole driver at the rehearsal's size. A program whose router
    reads what its experts read, gates by SiLU, turns the full layer's
    q and k too, leaves a window layer unturned, lets the window layers
    see every earlier key, leaves the chosen weights as the softmax
    over all experts gave them, or maps query heads to key/value heads
    by the remainder parts from the reference by more than a limit, and
    ``correct`` comes out false."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import prerouted_moe as model
    from paddle_tpu.ops import causal_attention as CA
    whole, routed = model.prerouted_moe_lm, fluid.layers.routed_experts
    with_kw = lambda fn, **fixed: lambda *a, **kw: fn(*a, **{**kw, **fixed})
    if fault == "the_router_reads_the_normed_stream":
        monkeypatch.setattr(fluid.layers, "routed_experts",
                            with_kw(routed, router_input=None))
    if fault == "silu_for_relu":      # under the op's own "relu"
        from paddle_tpu.parallel import moe
        monkeypatch.setitem(moe._GATES, "relu", jax.nn.silu)
    if fault == "rope_on_the_full_layer_too":
        monkeypatch.setattr(model, "prerouted_moe_lm",
                            with_kw(whole, rope_layout=[1, 1, 1, 1]))
    if fault == "no_rotation_on_a_window_layer":
        monkeypatch.setattr(model, "prerouted_moe_lm",
                            with_kw(whole, rope_layout=[0, 1, 0, 1]))
    if fault == "no_window_bound":
        monkeypatch.setattr(model, "prerouted_moe_lm",
                            with_kw(whole, window=1 << 30))
    if fault == "the_six_weights_not_normalised":
        monkeypatch.setattr(model, "prerouted_moe_lm",
                            with_kw(whole, norm_topk=False))
    if fault == "query_head_j_reads_key_head_j_mod_4":
        monkeypatch.setattr(CA, "causal_attention", _heads_read_j_mod(
            CA.causal_attention,
            _tiny_cell()["config_file"]["num_key_value_heads"]))
    said = []
    line = train_steps.run(_tiny_cell(), 7, 0.05, jax.devices("cpu"),
                           time.perf_counter(), None, said.append)
    assert line["failed"] == 0
    assert line["correct"] is (fault == "sound"), said
    counters = line["train"]["counters"]
    steps = counters["steps"][0]
    assert steps == line["train"]["steps"] + 2      # and the warm-up's
    # four layers, 4 x 512 rows, top-2, once a step
    assert sum(counters["expert_rows"]) == steps * 4 * 2048 * 2
    held = sum(counters["expert_rows"][:4])
    assert counters["expert_gate_units"] == [held * 32.0]
    assert 0.3 < counters["expert_gate_active"][0] / (held * 32) < 0.7


# -- the new readers on a window written out by hand -------------------------

def _op(kind, dur, op_name, start, kernel=False):
    text = "%%%s.1 = f32[8]{0} %s(...)" % (
        kind, "custom-call(...), custom_call_target=\"tpu_custom_call\""
        if kernel else "fusion")
    return spans.device_op(text, start, dur, op_name)


@pytest.fixture()
def window():
    """Two traced steps of a program ``step`` whose layers are
    recompute regions: per step the router's matmul, top-k and sort
    under the scope ``route``, forward, recomputed and backward, the
    expert layer's other glue, a window layer's and the full layer's
    flash kernels and a matmul, named as the compiler named them in the
    cell's trace on the chip (my chip run, PR 46)."""
    fwd = "jit(step)/jvp(%s)/"
    again = "jit(step)/transpose(jvp(jvp()))/checkpoint/" \
        "rematted_computation/%s/"
    bwd = "jit(step)/transpose(jvp(jvp()))/checkpoint/%s/"
    flash = lambda way, op, kind, jit, name: (
        name, (way % op) + kind + "/jit(%s)/%s/pallas_call:" % (jit, name))
    routed = "routed_experts.10"
    ops, at = [], 0.0
    for _ in range(2):
        for (kind, name), dur, kernel in (
                (("fusion", fwd % routed + "route/dot_general:"), 9e-4,
                 False),
                (("fusion", fwd % routed + "route/top_k:"), 5e-4, False),
                (("sort", fwd % routed + "route/sort:"), 7e-4, False),
                (("fusion", fwd % routed + "route/eq:;" + fwd % routed
                  + "route/reduce_sum:"), 2e-4, False),
                (("fusion", fwd % routed + "while/body/gather:"), 1e-3,
                 False),
                (flash(fwd, "causal_attention.7", "window", "_fwd_pallas",
                       "flash_fwd"), 8e-3, True),
                (flash(fwd, "causal_attention.33", "full", "_fwd_pallas",
                       "flash_fwd"), 16e-3, True),
                (("fusion", again % routed + "route/dot_general:"), 9e-4,
                 False),
                (("sort", again % routed + "route/sort:"), 7e-4, False),
                (flash(bwd, "causal_attention.33", "full", "_bwd_pallas",
                       "flash_bwd"), 32e-3, True),
                (flash(bwd, "causal_attention.7", "window", "_bwd_pallas",
                       "flash_bwd"), 13e-3, True),
                (("fusion", bwd % routed + "route/dot_general:"), 1e-3,
                 False),
                (("fusion", bwd % routed + "while/body/add:"), 2e-3, False),
                (("fusion", "jit(step)/jvp(mul.30)/dot_general:"), 3e-3,
                 False)):
            ops.append(_op(kind, dur, name, at, kernel))
            at += dur
    step_s = at / 2
    modules = [{"program": "step", "start": 0.0, "dur": step_s},
               {"program": "step", "start": step_s, "dur": step_s}]
    rows = [{"plane": DEV, "line": tracing.OP_LINE, "name": o["name"],
             "start": o["start"], "dur": o["dur"]} for o in ops]
    rows += [{"plane": DEV, "line": tracing.MODULE_LINE,
              "name": "jit_step(1)", "start": m["start"], "dur": m["dur"]}
             for m in modules]
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    return {"trace": tracing.reduce_rows(rows, 1),
            "spans": {"host": [], "ops": ops, "modules": modules,
                      "compiles": None},
            "config": cfg, "chips": 1,
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "train": {"batch": 1, "seq_len": 16384,
                      "tokens_per_step": 16384,
                      "counters": {"expert_rows": [1536] * 16 + [9] * 48,
                                   "steps": [1],
                                   "expert_gate_active": [9.0e6],
                                   "expert_gate_units": [16 * 1536 * 768.0],
                                   "window_scores_computed": [9.0e8],
                                   "window_scores_useful": [8.0e8]}}}


def test_new_readers_on_a_window_by_hand(window, capsys):
    busy = window["trace"]["busy_s"]
    read = lambda name: cells.load_metric(name).read(window)
    route = 2 * (9e-4 + 5e-4 + 7e-4 + 2e-4 + 9e-4 + 7e-4 + 1e-3)
    assert read("moe_route_dev_share_pct") == pytest.approx(
        100 * route / busy, rel=1e-9)
    said = capsys.readouterr().out
    assert ("matmul 0.005600, top_k 0.001000, sort 0.002800, other 0.000400"
            in said)
    # the scope lies under the expert layer's: its glue holds it
    assert read("moe_glue_dev_share_pct") == pytest.approx(
        100 * (route + 2 * 3e-3) / busy, rel=1e-9)
    assert read("expert_gate_active_pct") == pytest.approx(
        100 * 9.0e6 / (16 * 1536 * 768))
    assert read("expert_rows_max_over_mean") == pytest.approx(1.0)
    # the window's two readers are not on this cell's list (a test of
    # the benchmark's pins them to Trinity's cell); entries alone are
    # missing: both read this cell's runs as they are
    arch = cells.load_arch("smallthinker")
    peak = window["peaks"]["flops_bf16"]
    assert read("window_flash_roof_pct") == pytest.approx(
        100 * 2 * arch.window_flash_flops_per_step(window["config"], 1,
                                                   16384)
        / peak / (2 * 21e-3), rel=1e-9)
    assert read("window_scores_over_useful") == pytest.approx(1.125)
    assert read("flash_roof_pct") == pytest.approx(
        100 * 2 * arith.flash_flops_per_step(window["config"], 1, 16384)
        / peak / (2 * 69e-3), rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_a_program_without_the_layer(name):
    """In Trinity's cell on the parent of PR 46, which opens no scope
    ``route`` and counts no gate. The reader returns None and does not
    raise."""
    cfg = cells.load_cell(ROOT, "trinity_train_T16k")["config_file"]
    ops = [_op("fusion", 1e-3, "jit(step)/jvp(mul.3)/dot_general:", 0.0),
           _op("fusion", 1e-3,
               "jit(step)/jvp(routed_experts.4)/dot_general:", 1e-3),
           _op("sort", 1e-3, "jit(step)/jvp(routed_experts.4)/sort:", 2e-3)]
    modules = [{"program": "step", "start": 0.0, "dur": 3e-3}]
    run = {"trace": {"busy_s": 3e-3, "window_s": 3e-3},
           "spans": {"host": [], "ops": ops, "modules": modules,
                     "compiles": None},
           "config": cfg, "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite"),
           "train": {"batch": 1, "seq_len": 16384, "tokens_per_step": 16384,
                     "counters": {"expert_rows": [5] * 128, "steps": [1]}}}
    assert cells.load_metric(name).read(run) is None
    run.pop("trace")                   # an untraced run
    run["spans"] = None
    run["train"]["counters"] = {}
    assert cells.load_metric(name).read(run) is None
