"""The cell ``xing4_train_T4k`` (ISSUE 34): its files hold to their
source, the cell rehearses on the CPU through ``run.py``, a planted
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

CELL = "xing4_train_T4k"
DEV = "/device:TPU:0"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]


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
        if row["name"] == "Xing4.0-29B-A4B":
            assert cfg["published"] == row["config"]
            assert cfg["source"] == row["source_url"]
    assert cells.published_faults(cfg) == []
    assert cfg["reduced"] == REDUCED
    assert [cfg[key] for key in REDUCED] == [5, 1, 8, 16384, 0]
    # every width as published, and what the harness reads as one
    for key, value in cfg["published"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert (cfg["ep_size"], cfg["hc_mult"]) == (1, 4)
    assert cfg["num_experts"] == cfg["n_routed_experts"]
    for said in ("deployment", "assumed", "parameters", "train_dtype"):
        assert cfg[said]
    mix = cell["traffic_file"]
    assert (mix["batch"], mix["seq_len"], mix["n_batches"]) == (1, 4096, 4)
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"mla_flash_fwd_roof_pct", "mla_flash_bwd_roof_pct",
            "mla_glue_dev_share_pct", "hc_dev_share_pct", "flash_roof_pct",
            "expert_matmul_roof_pct", "moe_glue_dev_share_pct",
            "expert_rows_max_over_mean", "setup_enter_s"} <= names
    # norm_rope_dev_share_pct: its own test pins its list to one cell
    assert not {"flash_fwd_roof_pct", "flash_bwd_roof_pct",
                "matmul_roof_pct", "bd_noise_dev_share_pct",
                "norm_rope_dev_share_pct"} & names


def test_arithmetic_is_the_issues():
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    arch = cells.load_arch("xing")
    attention = (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192
                 + 4096 * 3584)
    assert round(attention / 1e4) == 2841          # 28.41 M
    hyper, expert = 2 * 14336 * 24, 3 * 3584 * 1024
    touched = (5 * (attention + hyper) + 3 * 3584 * 9216
               + 4 * (expert + 3584 * 64 + 0.5 * expert) + 3584 * 16384)
    assert arch.touched_parameters(cfg) == touched
    assert round(touched / 1e6) == 370
    assert arith.train_flops_per_token(cfg, 0) == 6 * touched
    assert arith.train_flops_per_token(cfg, 4096) == 6 * touched \
        + 2048 * 2 * (320 + 832) * 32 * 5
    # 2.22 + 0.76 GFLOP a token, 12.2 TFLOP a step of 4,096
    assert round(6 * touched / 1e7) == 222
    assert round(arith.train_flops_per_token(cfg, 4096) * 4096 / 1e11) == 122
    assert arith.flash_flops_per_step(cfg, 1, 4096) \
        == 4096 ** 2 // 2 * 2 * (320 + 832) * 32 * 5
    assert arch.flash_flops_split(cfg) == (320 / 1152, 832 / 1152)
    assert arch.expert_flops_per_pair(cfg) == 18 * 3584 * 1024
    assert arith.matmul_scopes(cfg) == ("mul",)


def test_the_cell_rehearses_through_run_py():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "the reference handed the program's choices" in p.stdout


@pytest.mark.parametrize("fault", [
    "sound", "no_routed_scaling_factor", "h_post_without_its_2",
    "rotary_part_dropped"])
def test_a_planted_fault_fails_correct(monkeypatch, fault):
    """The whole driver at the rehearsal's size. A program that leaves
    out ``routed_scaling_factor``, makes ``H_post`` a plain sigmoid, or
    drops the ``q_pe k_pe^T`` part of the score parts from the
    reference by more than a limit, and ``correct`` comes out false."""
    import jax
    if fault == "no_routed_scaling_factor":
        from paddle_tpu.models import latent_moe as model
        whole = model.latent_moe_lm
        monkeypatch.setattr(
            model, "latent_moe_lm",
            lambda **kw: whole(**{**kw, "routed_scaling_factor": 1.0}))
    if fault == "h_post_without_its_2":
        from paddle_tpu.ops import hyper_connection as hc
        sound = hc.coefficients

        def halved(*args, **kw):
            pre, post, res = sound(*args, **kw)
            return pre, post / 2.0, res
        monkeypatch.setattr(hc, "coefficients", halved)
    if fault == "rotary_part_dropped":
        from paddle_tpu.ops import latent_attention as la
        two_parts = la.flash_bthd
        monkeypatch.setattr(
            la, "flash_bthd", lambda *a, q2=None, k2=None, **kw:
            two_parts(*a, **kw))
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
    assert max(counters["selection_bias_abs_max"]) <= steps * 0.01 + 1e-9


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
    backward's ops of one latent attention and one hyper-connection,
    named as the compiler names them inside ``jax.checkpoint``."""
    fwd, again = "jit(step)/jvp()/checkpoint/", \
        "jit(step)/transpose(jvp())/checkpoint/rematted_computation/"
    bwd = "jit(step)/transpose(jvp())/checkpoint/"
    ops, at = [], 0.0
    for _ in range(2):
        for kind, dur, name, kernel in (
                ("fusion", 2e-4, fwd + "hyper_connection.3/dot_general",
                 False),
                ("fusion", 1e-4, fwd + "hyper_connection.3/sinkhorn/div",
                 False),
                ("copy", 3e-4, fwd + "mla_attention.9/concatenate", False),
                ("flash_fwd", 4e-3, fwd + "mla_attention.9/jit(_fwd_pallas2)"
                 "/flash_fwd", True),
                ("fusion", 6e-4, fwd + "hyper_connection.12/mul", False),
                ("flash_fwd", 4e-3, again + "mla_attention.9/"
                 "jit(_fwd_pallas2)/flash_fwd", True),
                ("fusion", 1e-4, again + "hyper_connection.3/sinkhorn/div",
                 False),
                ("flash_bwd_dq", 5e-3, bwd + "mla_attention.9/"
                 "jit(_bwd_pallas2)/flash_bwd_dq", True),
                ("flash_bwd_dkv", 6e-3, bwd + "mla_attention.9/"
                 "jit(_bwd_pallas2)/flash_bwd_dkv", True),
                ("fusion", 5e-4, bwd + "mla_attention.9/reduce_sum", False),
                ("fusion", 7e-4, bwd + "hyper_connection.3/transpose",
                 False),
                ("fusion", 3e-3, "jit(step)/jvp(mul.30)/dot_general",
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
            "train": {"batch": 1, "seq_len": 4096, "tokens_per_step": 4096,
                      "counters": {"expert_rows": [2048] * 8 + [7] * 56,
                                   "steps": [1]}}}


def test_new_readers_on_a_window_by_hand(window):
    busy = window["trace"]["busy_s"]
    read = lambda name: cells.load_metric(name).read(window)
    flops = arith.flash_flops_per_step(window["config"], 1, 4096)
    peak = window["peaks"]["flops_bf16"]
    # the forward runs twice a step under recompute: both are its time
    assert read("mla_flash_fwd_roof_pct") == pytest.approx(
        100 * 2 * flops * 320 / 1152 / peak / (2 * 8e-3), rel=1e-9)
    assert read("mla_flash_bwd_roof_pct") == pytest.approx(
        100 * 2 * flops * 832 / 1152 / peak / (2 * 11e-3), rel=1e-9)
    assert read("flash_roof_pct") == pytest.approx(
        100 * 2 * flops / peak / (2 * 19e-3), rel=1e-9)
    # inside a region an op's scope is its own, not the region's
    assert read("mla_glue_dev_share_pct") == pytest.approx(
        100 * 2 * (3e-4 + 5e-4) / busy, rel=1e-9)
    assert read("hc_dev_share_pct") == pytest.approx(
        100 * 2 * (2e-4 + 1e-4 + 6e-4 + 1e-4 + 7e-4) / busy, rel=1e-9)
    assert read("expert_rows_max_over_mean") == pytest.approx(1.0)


@pytest.mark.parametrize("name", [
    "mla_flash_fwd_roof_pct", "mla_flash_bwd_roof_pct",
    "mla_glue_dev_share_pct", "hc_dev_share_pct"])
def test_new_readers_find_nothing_in_a_program_without_the_layer(name):
    """In OPT's cell, or on a parent whose architecture states no
    split: no such scope. The reader returns None and does not
    raise."""
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
