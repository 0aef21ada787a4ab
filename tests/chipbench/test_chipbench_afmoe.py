"""The cell ``trinity_train_T16k`` (ISSUE 38): its files hold to their
source, the cell rehearses on the CPU through ``run.py``, each planted
fault fails ``correct`` through the driver, the arithmetic is the
issue's, and each new reader reads a small window written out by
hand."""

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

CELL = "trinity_train_T16k"
DEV = "/device:TPU:0"
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size", "layer_types"]
SLIDING, FULL = "sliding_attention", "full_attention"


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
        if row["name"] == "Trinity-Mini":
            assert cfg["published"] == row["config"]
            assert cfg["source"] == row["source_url"]
    assert cells.published_faults(cfg) == []
    assert cfg["reduced"] == REDUCED
    assert [cfg[key] for key in REDUCED] == [
        5, 1, 8, 25024, [SLIDING, SLIDING, SLIDING, FULL, SLIDING]]
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:5]
    # every width as published, and what the harness reads as one
    for key, value in cfg["published"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert (cfg["sliding_window"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["published"]["num_experts"], cfg["route_scale"],
            cfg["load_balance_coeff"]) == (2048, 128, 6144, 1024, 8, 128,
                                           2.826, 0.001)
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["first_expert"] == 0 and "16 chips" in cfg["deployment"]
    for said in ("deployment", "assumed", "parameters", "train_dtype"):
        assert cfg[said]
    tiny = {**cfg, **cfg["rehearse"]}
    assert tiny["sliding_window"] < cell["traffic_file"]["rehearse"]["seq_len"]
    assert {SLIDING, FULL} == set(tiny["layer_types"])
    assert tiny["num_dense_layers"] == 1
    mix = cell["traffic_file"]
    assert (mix["batch"], mix["seq_len"], mix["n_batches"],
            mix["check_rows"]) == (1, 16384, 4, 64)
    assert cell["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"window_flash_roof_pct", "window_scores_over_useful",
            "gate_norm_dev_share_pct", "flash_roof_pct", "train_mfu_pct",
            "expert_matmul_roof_pct", "moe_glue_dev_share_pct",
            "expert_rows_max_over_mean", "setup_enter_s",
            "step_interval_ms.train"} <= names
    # norm_rope_dev_share_pct: its own test pins its list to one cell
    assert not {"flash_fwd_roof_pct", "flash_bwd_roof_pct",
                "matmul_roof_pct", "bd_noise_dev_share_pct",
                "norm_rope_dev_share_pct", "hc_dev_share_pct"} & names
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for name in ("window_flash_roof_pct", "window_scores_over_useful",
                 "gate_norm_dev_share_pct"):
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"]) == ("kernels",
                                                    "tokens_per_s")


def test_arithmetic_is_the_issues():
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    arch = cells.load_arch("afmoe")
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert round(attention / 1e4) == 2726          # 27.26 M
    expert = 3 * 2048 * 1024
    touched = (5 * attention + 3 * 2048 * 6144
               + 4 * (expert + 2048 * 128 + 0.5 * expert) + 2048 * 25024)
    assert arch.touched_parameters(cfg) == touched
    assert arith.train_flops_per_token(cfg, 0) == 6 * touched
    t, w = 16384, 2048
    causal, band = t * (t + 1) // 2, w * (w + 1) // 2 + (t - w) * w
    assert arch.useful_scores(t) == causal
    assert arch.useful_scores(t, w) == band
    assert arch.useful_scores(t, t) == arch.useful_scores(t, 2 * t) == causal
    assert arch.useful_scores(64, 16) == sum(min(i + 1, 16)
                                             for i in range(64))
    per_score = 14 * 128
    assert arith.flash_flops_per_step(cfg, 1, t) \
        == per_score * 32 * (causal + 4 * band)
    assert arch.window_flash_flops_per_step(cfg, 1, t) \
        == per_score * 32 * 4 * band
    assert arith.train_flops_per_token(cfg, t) == pytest.approx(
        6 * touched + arith.flash_flops_per_step(cfg, 1, t) / t)
    # the issue's reckoning: 40.9 TFLOP a step, 26.0 in the matmuls, 7.7
    # in the one full layer's attention and 7.2 in the four window
    # layers'; without the bound those four would cost 30.8
    step = arith.train_flops_per_token(cfg, t) * t
    assert [round(x / 1e11) for x in (
        step, 6 * touched * t, per_score * 32 * causal,
        arch.window_flash_flops_per_step(cfg, 1, t),
        per_score * 32 * 4 * causal)] == [409, 260, 77, 72, 308]
    assert arch.expert_flops_per_pair(cfg) == 18 * 2048 * 1024
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
    # the dense form ran: no band was walked, and nothing is read
    assert set(last["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "the reference handed the program's choices" in p.stdout


@pytest.mark.parametrize("fault", [
    "sound", "no_window_on_the_sliding_layers", "rope_on_the_full_layer_too",
    "no_output_gate", "no_route_scale", "no_post_norms",
    "no_sqrt_d_on_the_embedding"])
def test_a_planted_fault_fails_correct(monkeypatch, fault):
    """The whole driver at the rehearsal's size. A program that lets
    the sliding layers see every earlier key, turns the full layer's q
    and k too, leaves the output gate, ``route_scale``, the two
    post-norms or the embedding's ``sqrt(d)`` out parts from the
    reference by more than a limit, and ``correct`` comes out false."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import windowed_moe as model
    whole = model.windowed_moe_lm
    if fault == "no_window_on_the_sliding_layers":
        monkeypatch.setattr(model, "windowed_moe_lm", lambda **kw: whole(
            **{**kw, "window": 1 << 30}))
    if fault == "rope_on_the_full_layer_too":
        sound = fluid.layers.qk_norm_rope
        monkeypatch.setattr(
            fluid.layers, "qk_norm_rope",
            lambda *a, **kw: sound(*a, **{**kw, "rotate": True}))
    if fault == "no_output_gate":
        # the gate's projection stays in the graph and weighs nothing
        monkeypatch.setattr(
            fluid.layers, "sigmoid_mul",
            lambda x, y: fluid.layers.elementwise_add(
                x, fluid.layers.scale(y, 0.0)))
    if fault == "no_route_scale":
        monkeypatch.setattr(model, "windowed_moe_lm", lambda **kw: whole(
            **{**kw, "route_scale": 1.0}))
    if fault == "no_post_norms":
        sound = model._norm

        def norm(x, name, eps):     # its weight stays, and weighs alone
            y = sound(x, name, eps)
            return fluid.layers.elementwise_add(
                x, fluid.layers.scale(y, 0.0)) \
                if name.endswith("_post") else y
        monkeypatch.setattr(model, "_norm", norm)
    if fault == "no_sqrt_d_on_the_embedding":
        monkeypatch.setattr(fluid.layers, "scale", lambda x, scale: x)
    said = []
    line = train_steps.run(_tiny_cell(), 7, 0.05, jax.devices("cpu"),
                           time.perf_counter(), None, said.append)
    assert line["failed"] == 0
    assert line["correct"] is (fault == "sound"), said
    counters = line["train"]["counters"]
    steps = counters["steps"][0]
    assert steps == line["train"]["steps"] + 2      # and the warm-up's
    # two routed layers, 4 x 512 rows, top-2, once a step
    assert sum(counters["expert_rows"]) == steps * 2 * 2048 * 2
    assert max(counters["selection_bias_abs_max"]) <= steps * 1e-3 + 1e-9


# -- the new readers on a window written out by hand -------------------------

def _op(kind, dur, op_name, start, kernel=False):
    text = "%%%s.1 = f32[8]{0} %s(...)" % (
        kind, "custom-call(...), custom_call_target=\"tpu_custom_call\""
        if kernel else "fusion")
    return spans.device_op(text, start, dur, op_name)


@pytest.fixture()
def window():
    """Two traced steps of a program ``step`` whose layers are
    recompute regions: per step the forward's, the recomputed and the
    backward's kernels of one window layer and one full layer, two
    norms, the gate and a matmul, named as the compiler named them in
    the cell's trace on the chip (my chip run, PR 38)."""
    fwd = "jit(step)/jvp(%s)/"
    again = "jit(step)/transpose(jvp(jvp()))/checkpoint/" \
        "rematted_computation/%s/"
    bwd = "jit(step)/transpose(jvp(jvp()))/checkpoint/%s/"
    flash = lambda way, op, kind, jit, name: (
        name, (way % op) + kind + "/jit(%s)/%s/pallas_call:" % (jit, name))
    ops, at = [], 0.0
    for _ in range(2):
        for (kind, name), dur, kernel in (
                (("fusion", fwd % "rms_norm.4" + "mul"), 4e-4, False),
                (flash(fwd, "causal_attention.10", "window", "_fwd_pallas",
                       "flash_fwd"), 6e-3, True),
                (("fusion", fwd % "sigmoid_mul.11" + "mul"), 2e-4, False),
                (("fusion", fwd % "rms_norm.13" + "mul"), 3e-4, False),
                (flash(fwd, "causal_attention.74", "full", "_fwd_pallas",
                       "flash_fwd"), 19e-3, True),
                (flash(again, "causal_attention.74", "full", "_fwd_pallas",
                       "flash_fwd"), 19e-3, True),
                (flash(bwd, "causal_attention.74", "full", "_bwd_pallas",
                       "flash_bwd_dq"), 22e-3, True),
                (flash(bwd, "causal_attention.74", "full", "_bwd_pallas",
                       "flash_bwd_dkv"), 28e-3, True),
                (flash(again, "causal_attention.10", "window", "_fwd_pallas",
                       "flash_fwd"), 6e-3, True),
                (("fusion", again % "rms_norm.4" + "mul"), 4e-4, False),
                (flash(bwd, "causal_attention.10", "window", "_bwd_pallas",
                       "flash_bwd_dq"), 5.5e-3, True),
                (flash(bwd, "causal_attention.10", "window", "_bwd_pallas",
                       "flash_bwd_dkv"), 7e-3, True),
                (("fusion", bwd % "sigmoid_mul.11" + "mul"), 5e-4, False),
                (("fusion", bwd % "rms_norm.4" + "reduce_sum"), 6e-4, False),
                (("fusion", "jit(step)/jvp(mul.30)/dot_general"), 3e-3,
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
                      "counters": {"expert_rows": [1024] * 8 + [7] * 120,
                                   "steps": [1],
                                   "window_scores_computed": [9.0e8],
                                   "window_scores_useful": [8.0e8]}}}


def test_new_readers_on_a_window_by_hand(window):
    busy = window["trace"]["busy_s"]
    read = lambda name: cells.load_metric(name).read(window)
    arch = cells.load_arch("afmoe")
    peak = window["peaks"]["flops_bf16"]
    band = arch.window_flash_flops_per_step(window["config"], 1, 16384)
    # the window layers' kernels alone, the forward's two runs both
    # their time; the full layer's are flash_roof_pct's beside them
    assert read("window_flash_roof_pct") == pytest.approx(
        100 * 2 * band / peak / (2 * 24.5e-3), rel=1e-9)
    assert read("flash_roof_pct") == pytest.approx(
        100 * 2 * arith.flash_flops_per_step(window["config"], 1, 16384)
        / peak / (2 * 112.5e-3), rel=1e-9)
    assert read("window_scores_over_useful") == pytest.approx(1.125)
    # inside a region an op's scope is its own, not the region's
    assert read("gate_norm_dev_share_pct") == pytest.approx(
        100 * 2 * (7e-4 + 17e-4) / busy, rel=1e-9)
    assert read("expert_rows_max_over_mean") == pytest.approx(1.0)


@pytest.mark.parametrize("name", [
    "window_flash_roof_pct", "window_scores_over_useful",
    "gate_norm_dev_share_pct"])
def test_new_readers_find_nothing_in_a_program_without_the_layer(name):
    """In OPT's cell, or on a parent that counts no band and opens no
    such scope. The reader returns None and does not raise."""
    cfg = cells.load_cell(ROOT, "opt350m_train")["config_file"]
    ops = [_op("fusion", 1e-3, "jit(step)/jvp(mul.3)/dot_general", 0.0),
           _op("flash_fwd", 1e-3, "jit(step)/jvp(sp_attention.4)/flash_fwd",
               1e-3, True)]
    modules = [{"program": "step", "start": 0.0, "dur": 2e-3}]
    run = {"trace": {"busy_s": 2e-3, "window_s": 2e-3},
           "spans": {"host": [], "ops": ops, "modules": modules,
                     "compiles": None},
           "config": cfg, "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite"),
           "train": {"batch": 4, "seq_len": 2048, "tokens_per_step": 8192,
                     "counters": {}}}
    assert cells.load_metric(name).read(run) is None
    run.pop("trace")                   # an untraced run
    run["spans"] = None
    assert cells.load_metric(name).read(run) is None
