"""The cell ``sdar_train_bd4k`` (ISSUE 32): its files hold to their
source, the cell rehearses on the CPU through ``run.py``, a router that
is WRONG fails ``correct``, the arithmetic is the issue's, and each new
reader reads a small window written out by hand."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import arith, cells, peaks, spans, tracing   # noqa: E402
from chipbench.drivers import train_steps                   # noqa: E402
from chipbench.reference import compare                     # noqa: E402

CELL = "sdar_train_bd4k"
DEV = "/device:TPU:0"


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
        if row["name"] == "SDAR-30B-A3B-Chat":
            assert cfg["published"] == row["config"]
            assert cfg["source"] == row["source_url"]
    assert cells.published_faults(cfg) == []
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    # every width as published
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok"):
        assert cfg[key] == cfg["published"][key]
    assert cell["traffic_file"]["batch"] * cell["traffic_file"]["seq_len"] \
        == 8192
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"expert_matmul_roof_pct", "moe_glue_dev_share_pct",
            "expert_rows_max_over_mean", "bd_noise_dev_share_pct",
            "flash_roof_pct", "setup_enter_s"} <= names
    assert "attn_glue_dev_share_pct" not in names


def test_arithmetic_is_the_issues():
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    arch = cells.load_arch("sdar")
    row = 2 * 2048 * (4096 + 1024 + 4096) + 2 * 2048 * 128 + 6 * 2048 * 768
    assert row == 37748736 + 524288 + 9437184 == arch._row_flops(cfg)
    head = 2 * 2048 * 18992
    assert arith.train_flops_per_token(cfg, 0) == 3 * (4 * 2 * row + head)
    assert arith.train_flops_per_token(cfg, 4096) == 3 * (
        4 * (2 * row + 4 * (4096 + 4) * 4096) + head)
    # 2.185 GFLOP a clean token, 17.9 TFLOP a step of 8,192
    assert round(arith.train_flops_per_token(cfg, 4096) / 1e6) == 2185
    assert arith.flash_flops_per_step(cfg, 2, 4096) \
        == 7 * 2 * 4096 ** 2 * 32 * 128 * 4 * 2
    assert arch.expert_flops_per_pair(cfg) == 18 * 2048 * 768
    # attention 41%, the projections 47%, the held experts 12% of a layer
    layer = 2 * row + 4 * 4100 * 4096
    assert round(100 * 4 * 4100 * 4096 / layer) == 41
    assert round(100 * 2 * 9437184 / layer) == 12
    # the weights held here, once, and 2 x 4 x 128 values a token and layer
    more = arith.decode_step_bytes(cfg, 2, 1001, 1) \
        - arith.decode_step_bytes(cfg, 2, 1, 1)
    assert more == 2 * 1000 * 4 * 2 * 4 * 128
    assert arith.decode_step_bytes(cfg, 2, 0, 0) > 2 * 4 * 16 * 3 * 2048 * 768
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


@pytest.mark.parametrize("router", ["sound", "wrong"])
def test_a_wrong_router_fails_correct(monkeypatch, router):
    """The program's forward against the reference at the rehearsal's
    size. Sound: under the limit, and counted. With a router that takes
    the k LEAST probable experts the reference, handed those choices,
    finds none of them near its own cut, routes by itself and the
    logits are apart by far more than the limit."""
    import jax
    from paddle_tpu.parallel import moe
    if router == "wrong":
        def least(x, router_w, top_k, norm_topk):
            probs, _, _ = moe_route(x, router_w, top_k, norm_topk)
            neg, idx = jax.lax.top_k(-probs, top_k)
            return probs, -neg / (-neg).sum(-1, keepdims=True), idx
        moe_route = moe.route
        monkeypatch.setattr(moe, "route", least)
    cell = _tiny_cell()
    cfg, mix = cell["config_file"], cell["traffic_file"]
    arch = cells.load_arch("sdar")
    from chipbench import traffic
    with train_steps.trainer(cell, 5, False) as t:
        (first,) = train_steps.declared_feeds(t.main, traffic.lm_batches(
            5, 1, 1, mix["seq_len"], cfg["vocab_size"]))
        assert set(first) == {"src", "label", "mask"}
        params = arch.params_of_program(t.main, t.scope, cfg)
        got, ref, choices = train_steps.forward_against_reference(
            t, params, first, mix["check_rows"])
        counters = arch.program_counters(t.main, t.scope)
    assert choices.shape == (cfg["num_hidden_layers"], 1,
                             2 * mix["seq_len"], cfg["num_experts_per_tok"])
    # a for_test run counts nothing
    assert counters == {"expert_rows": [0] * 16, "steps": [0]}
    err = compare.logits_error(got, ref)
    if router == "sound":
        assert err < arch.TRAIN_LOGITS_RTOL
    else:
        assert err > 3 * arch.TRAIN_LOGITS_RTOL


@pytest.mark.parametrize("fault", ["sound", "no_auxiliary_loss",
                                   "no_1_over_t"])
def test_a_wrong_objective_fails_correct(monkeypatch, fault):
    """The whole driver at the rehearsal's size. A program that leaves
    out the routers' load-balancing loss, or the 1/t weights of the
    masked tokens, has sound logits: its FIRST LOSS is what parts from
    the reference's, and ``LOSS_RTOL`` has to catch it."""
    import time
    import jax
    from paddle_tpu.models import block_diffusion as model
    if fault == "no_auxiliary_loss":
        whole = model.block_diffusion_lm
        monkeypatch.setattr(
            model, "block_diffusion_lm",
            lambda **kw: whole(**{**kw, "aux_weight": 0.0}))
    if fault == "no_1_over_t":
        weighted = model.lm_cost
        monkeypatch.setattr(
            model, "lm_cost", lambda logits, label, mask, vocab, weight:
            weighted(logits, label, mask, vocab))
    said = []
    line = train_steps.run(_tiny_cell(), 7, 0.05, jax.devices("cpu"),
                           time.perf_counter(), None, said.append)
    (loss,) = [text for text in said if text.startswith("first loss")]
    err = float(loss.split("relative error ")[1].split()[0])
    arch = cells.load_arch("sdar")
    assert line["failed"] == 0
    if fault == "sound":
        assert line["correct"] is True and err < arch.LOSS_RTOL
    else:
        assert line["correct"] is False and err > 3 * arch.LOSS_RTOL


# -- the new readers on a window written out by hand -------------------------

def _op(kind, dur, op_name, start, kernel=False):
    text = "%%%s.1 = f32[8]{0} %s(...)" % (
        kind, "custom-call(...), custom_call_target=\"tpu_custom_call\""
        if kernel else "fusion")
    return spans.device_op(text, start, dur, op_name)


@pytest.fixture()
def window():
    """Two traced steps of a program ``step``: per step a noise draw,
    attention's two kernels and its merge, the expert layer's router
    and gathers, two grouped matmuls (unscoped, as XLA leaves them) and
    a projection."""
    ops, at = [], 0.0
    for _ in range(2):
        for kind, dur, name, kernel in (
                ("fusion", 1e-4, "jit(step)/jvp(block_diffusion_noise.3)/x",
                 False),
                ("flash_fwd", 4e-3,
                 "jit(step)/jvp(block_diffusion_attention.20)/flash_fwd",
                 True),
                ("fusion", 5e-4,
                 "jit(step)/jvp(block_diffusion_attention.20)/exp", False),
                ("flash_bwd_dq", 6e-3, "jit(step)/transpose(jvp("
                 "block_diffusion_attention.20))/flash_bwd_dq", True),
                ("fusion", 7e-4, "jit(step)/transpose(jvp("
                 "block_diffusion_attention.20))/mul", False),
                ("fusion", 9e-4, "jit(step)/jvp(routed_experts.25)/top_k",
                 False),
                ("gather", 3e-4, "jit(step)/transpose(jvp("
                 "routed_experts.25))/while/body/gather", False),
                ("ragged-dot-none", 2e-3, "ragged-dot-none", True),
                ("ragged-dot-metadata", 1e-5, "ragged-dot-metadata", True),
                ("fusion", 3e-3, "jit(step)/jvp(mul.30)/dot_general", False)):
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
    # 10 train steps; the held experts (ids 0-15) took 1000 rows each a
    # step but one, which took 3000
    held = [10000] * 15 + [30000]
    return {"trace": tracing.reduce_rows(rows, 1),
            "spans": {"host": [], "ops": ops, "modules": modules,
                      "compiles": None},
            "config": cfg, "chips": 1,
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "train": {"batch": 2, "seq_len": 4096, "tokens_per_step": 8192,
                      "counters": {"expert_rows": held + [7] * 112,
                                   "steps": [10]}}}


def test_new_readers_on_a_window_by_hand(window):
    busy = window["trace"]["busy_s"]
    read = lambda name: cells.load_metric(name).read(window)
    # 18,000 pairs a step on held experts, two traced steps, the four
    # grouped-matmul rows' time
    want = 100 * 2 * 18000 * 18 * 2048 * 768 \
        / window["peaks"]["flops_bf16"] / (2 * (2e-3 + 1e-5))
    assert read("expert_matmul_roof_pct") == pytest.approx(want, rel=1e-9)
    assert read("moe_glue_dev_share_pct") == pytest.approx(
        100 * 2 * (9e-4 + 3e-4) / busy, rel=1e-9)
    assert read("bd_noise_dev_share_pct") == pytest.approx(
        100 * 2 * (1e-4 + 5e-4 + 7e-4) / busy, rel=1e-9)
    assert read("expert_rows_max_over_mean") == pytest.approx(
        30000 / (180000 / 16), rel=1e-12)
    # the kernels of the attention op are flash time, by name
    assert read("flash_roof_pct") == pytest.approx(
        100 * 2 * arith.flash_flops_per_step(window["config"], 2, 4096)
        / window["peaks"]["flops_bf16"] / (2 * (4e-3 + 6e-3)), rel=1e-9)


@pytest.mark.parametrize("name", [
    "expert_matmul_roof_pct", "moe_glue_dev_share_pct",
    "expert_rows_max_over_mean", "bd_noise_dev_share_pct"])
def test_new_readers_find_nothing_in_a_program_without_the_layer(name):
    """On the parent, or in OPT's cell: no counters, no such scope, no
    such kernel. The reader returns None and does not raise."""
    cfg = cells.load_cell(ROOT, "opt350m_train")["config_file"]
    ops = [_op("fusion", 1e-3, "jit(step)/jvp(mul.3)/dot_general", 0.0)]
    modules = [{"program": "step", "start": 0.0, "dur": 1e-3}]
    run = {"trace": {"busy_s": 1e-3, "window_s": 1e-3},
           "spans": {"host": [], "ops": ops, "modules": modules,
                     "compiles": None},
           "config": cfg, "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite"),
           "train": {"batch": 4, "seq_len": 2048, "tokens_per_step": 8192,
                     "counters": {}}}
    assert cells.load_metric(name).read(run) is None
    run["train"].pop("counters")       # the parent's driver: no such key
    run.pop("trace")                   # and an untraced run
    assert cells.load_metric(name).read(run) is None
