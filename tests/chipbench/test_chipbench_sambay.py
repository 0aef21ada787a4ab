"""The cell ``phi4flash_train_T8k`` (ISSUE 40): its files hold to their
source, the cell rehearses on the CPU through ``run.py`` with the scan's
chunked kernels in interpret mode, each planted fault fails ``correct``
through the driver, the arithmetic is the issue's, and each new reader
reads a small window written out by hand."""

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

CELL = "phi4flash_train_T8k"
DEV = "/device:TPU:0"
REDUCED = ["num_hidden_layers", "vocab_size"]
KINDS = ["mamba", "sliding", "mamba_memory", "full", "gmu", "cross"]
NEW = ("scan_hbm_roof_pct", "ssm_glue_dev_share_pct",
       "diff_attn_glue_dev_share_pct")


def _tiny_cell():
    """The cell cut to its rehearsal size, as ``run.load_cell`` cuts it
    (the scans as the chunked kernels in interpret mode, by the
    configuration's own ``scan_force``; attention the dense form)."""
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
        if row["name"] == "Phi-4-mini-flash-reasoning":
            assert cfg["published"] == row["config"]
            assert cfg["source"] == row["source_url"]
    assert cells.published_faults(cfg) == []
    assert cfg["reduced"] == REDUCED
    assert [cfg[key] for key in REDUCED] == [6, 25008]
    # every width as published, and what the harness reads as one
    for key, value in cfg["published"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["sliding_window"], cfg["mb_per_layer"],
            cfg["tie_word_embeddings"], cfg["layer_norm_eps"]) == (
                2560, 10240, 40, 20, 512, 2, True, 1e-5)
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["layer_kinds"] == KINDS and "layer_kinds" in cfg["assumed"]
    assert cfg["mamba"] == {"expand": 2, "d_state": 16, "d_conv": 4,
                            "dt_rank": 160}
    assert "8 chips" in cfg["deployment"] and "8 : 1 : 7" in cfg["deployment"]
    for said in ("deployment", "assumed", "parameters", "train_dtype"):
        assert cfg[said]
    for line in cfg["assumed"].values():
        assert isinstance(line, str) and line
    tiny = {**cfg, **cfg["rehearse"]}
    mix = cell["traffic_file"]
    assert tiny["layer_kinds"] == KINDS       # all six kinds rehearse
    assert tiny["sliding_window"] < mix["rehearse"]["seq_len"]
    assert mix["rehearse"]["seq_len"] >= 4 * tiny["scan_chunk"]
    assert tiny["scan_force"] == "interpret" and "scan_force" not in cfg
    assert (mix["batch"], mix["seq_len"], mix["n_batches"],
            mix["warmup_steps"], mix["trace_steps"], mix["check_rows"]) == (
                1, 8192, 4, 3, 6, 64)
    assert cell["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {
        "flash_roof_pct", "train_mfu_pct", "matmul_roof_pct",
        "device_idle_pct.train", "setup_enter_s", "step_interval_ms.train",
        "xent_dev_share_pct", "optimizer_dev_share_pct"} <= names
    assert not {"window_flash_roof_pct", "window_scores_over_useful",
                "gate_norm_dev_share_pct", "norm_rope_dev_share_pct",
                "expert_matmul_roof_pct", "moe_glue_dev_share_pct",
                "flash_fwd_roof_pct", "hc_dev_share_pct"} & names
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for name in NEW:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"], entry["source"]) == (
            "kernels", "tokens_per_s", "device_trace")


def test_arithmetic_is_the_issues():
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    arch = cells.load_arch("sambay")
    d, f, di = 2560, 10240, 5120
    mlp = 3 * d * f
    mamba = 2 * d * di + di * (160 + 32) + 160 * di + di * d
    attention, gmu, cross = 2 * d * d + 2 * d * 1280, 2 * d * di, 2 * d * d
    # the issue's: MLP 78.64 M; mixers 41.1, 19.66, 26.21 and 13.11 M
    assert [round(x / 1e4) for x in (mlp, mamba, attention, gmu, cross)] \
        == [7864, 4112, 1966, 2621, 1311]
    assert [arch.mixer_parameters(cfg, kind) for kind in KINDS] == [
        mamba, attention, mamba, attention, gmu, cross]
    touched = 6 * mlp + 2 * mamba + 2 * attention + gmu + cross + d * 25008
    assert arch.touched_parameters(cfg) == touched
    assert round(touched / 1e6) == 697
    assert arith.train_flops_per_token(cfg, 0) == 6 * touched
    t, w = 8192, 512
    causal, band = t * (t + 1) // 2, w * (w + 1) // 2 + (t - w) * w
    assert arch.useful_scores(t) == causal
    assert arch.useful_scores(t, w) == band
    assert arch.useful_scores(64, 16) == sum(min(i + 1, 16)
                                             for i in range(64))
    # a softmax's score: key 64, value 128: 8 x 64 + 6 x 128 = 20 x 64
    per_score = 8 * 64 + 6 * 128
    assert arch._score_flops(cfg) == per_score
    assert arith.flash_flops_per_step(cfg, 1, t) \
        == per_score * 40 * (2 * causal + band)
    assert arith.train_flops_per_token(cfg, t) == pytest.approx(
        6 * touched + arith.flash_flops_per_step(cfg, 1, t) / t)
    # 37.9 TFLOP a step: 34.2 in the matmuls, 3.4 in the full and the
    # cross layer's attention, 0.2 in the window layer's
    step = arith.train_flops_per_token(cfg, t) * t
    assert [round(x / 1e11) for x in (
        step, 6 * touched * t, per_score * 40 * 2 * causal,
        per_score * 40 * band)] == [379, 342, 34, 2]
    # the scans: 0.67 G state updates a layer-pass, six passes a step;
    # 11 [T, C] and 8 [T, N] bf16 values a Mamba layer
    assert arch.scan_updates_per_step(cfg, 1, t) == 6 * t * di * 16
    assert round(t * di * 16 / 1e7) == 67
    assert arch.scan_bytes_per_step(cfg, 1, t) \
        == 2 * t * 2 * (11 * di + 8 * 16)
    assert arch.scan_bytes_per_step(cfg, 2, t, 4) \
        == 4 * arch.scan_bytes_per_step(cfg, 1, t)
    assert arith.matmul_scopes(cfg) == ("mul",)


# at the rehearsal's size (1,024 tokens a step, hidden 64) the first
# loss's error reads 5e-5 to 4e-4 by the seed, where the cell's own size
# reads 6e-6 to 1.4e-5: these seeds are ones that leave the limits room
@pytest.mark.parametrize("seed", ["3000000019", "2200000017"])
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


def _faults(monkeypatch, fault):
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.models import hybrid_ssm as model
    from paddle_tpu.ops import diff_attention as DA
    from paddle_tpu.ops import selective_scan as SS
    whole, scan, join = (model.hybrid_ssm_lm, SS.selective_scan,
                         DA.diff_combine)
    if fault == "no_convolution":
        import jax
        monkeypatch.setattr(SS, "causal_conv_silu",
                            lambda x, w, bias: jax.nn.silu(x))
    if fault == "no_d_times_s":
        monkeypatch.setattr(SS, "selective_scan", lambda *a, **kw: scan(
            *a[:5], jnp.zeros_like(a[5]), **kw))
    if fault == "the_memory_after_its_gate":
        gate, mixer, gated = fluid.layers.ssm_gate, model.mamba_mixer, []

        def remember(y, z, name=None):
            gated.append(gate(y, z, name))
            return gated[-1]
        monkeypatch.setattr(fluid.layers, "ssm_gate", remember)
        monkeypatch.setattr(model, "mamba_mixer",
                            lambda *a: (mixer(*a)[0], gated[-1]))
    if fault == "cross_reads_its_own_keys":
        mixer = model.attention_mixer
        monkeypatch.setattr(
            model, "attention_mixer", lambda h, name, kind, *a: mixer(
                h, name, model.FULL if kind == model.CROSS else kind, *a))
    if fault == "no_window_on_the_sliding_layer":
        monkeypatch.setattr(model, "hybrid_ssm_lm", lambda **kw: whole(
            **{**kw, "window": 1 << 30}))
    if fault == "lam_fixed_at_0":
        monkeypatch.setattr(DA, "diff_combine", lambda a1, a2, *rest: join(
            a1, jnp.zeros_like(a2), *rest))
    if fault == "no_one_minus_lam0":
        monkeypatch.setattr(DA, "diff_combine",
                            lambda *a: join(*a) / (1.0 - a[7]))
    if fault == "the_state_reset_at_every_chunk":
        def chunks(s, dt, a, b, c, d, chunk=None, **kw):
            cut = lambda x, i: x[:, i:i + chunk]
            return jnp.concatenate([
                scan(cut(s, i), cut(dt, i), a, cut(b, i), cut(c, i), d,
                     chunk=chunk, **kw)
                for i in range(0, s.shape[1], chunk)], 1)
        monkeypatch.setattr(SS, "selective_scan", chunks)


@pytest.mark.parametrize("fault", [
    "sound", "no_convolution", "no_d_times_s", "the_memory_after_its_gate",
    "cross_reads_its_own_keys", "no_window_on_the_sliding_layer",
    "lam_fixed_at_0", "no_one_minus_lam0", "the_state_reset_at_every_chunk"])
def test_a_planted_fault_fails_correct(monkeypatch, fault):
    """The whole driver at the rehearsal's size. A program that leaves
    the convolution or ``D * s`` out, hands the gated memory unit the
    memory AFTER its gate, lets the cross layer read keys and values of
    its own, lets the sliding layer see every earlier key, fixes ``lam``
    at 0, leaves the ``1 - lam0`` scale out or starts the scan's state
    anew at every chunk parts from the reference by more than a limit,
    and ``correct`` comes out false."""
    import jax
    _faults(monkeypatch, fault)
    said = []
    line = train_steps.run(_tiny_cell(), 11, 0.05, jax.devices("cpu"),
                           time.perf_counter(), None, said.append)
    assert line["failed"] == 0
    assert line["correct"] is (fault == "sound"), said
    counters = line["train"]["counters"]
    # the scans ran as the chunked kernels, never as the step loop
    assert not any(key.startswith("steps/")
                   for key in counters["scan_lowerings"])
    assert counters["scan_lowerings"]["interpret/bwd"] >= 2


@pytest.mark.parametrize("control", ["control_logits_at",
                                     "bf16_state_logits_at"])
def test_a_control_parts_from_the_reference(control):
    """Both controls of ``TRAIN_LOGITS_RTOL`` (fp8 operands in every
    matmul; the scan's state held in bfloat16 between steps) run on the
    program's own parameters at the rehearsal's size and give logits of
    the reference's shape that are NOT the reference's."""
    import numpy as np
    from chipbench import traffic
    from chipbench.reference import compare
    cell = _tiny_cell()
    cfg, mix = cell["config_file"], cell["traffic_file"]
    rows = mix["check_rows"]
    with train_steps.trainer(cell, 13, False) as t:
        params = t.arch.params_of_program(t.main, t.scope, cfg)
    one = traffic.lm_batches(13, 1, 1, mix["seq_len"], cfg["vocab_size"])[0]
    want, got = (train_steps.reference_rows(fn, cfg, params, one, rows)
                 for fn in (t.arch.logits_at, getattr(t.arch, control)))
    assert got.shape == want.shape == (rows, cfg["vocab_size"])
    assert np.isfinite(got).all()
    assert compare.logits_error(got, want) > 1e-5


# -- the new readers on a window written out by hand -------------------------

def _op(kind, dur, op_name, start, kernel=False):
    text = "%%%s.1 = f32[8]{0} %s(...)" % (
        kind, "custom-call(...), custom_call_target=\"tpu_custom_call\""
        if kernel else "fusion")
    return spans.device_op(text, start, dur, op_name)


@pytest.fixture()
def window():
    """Two traced steps of a program ``step`` whose layers are
    recompute regions: per step a Mamba layer's convolution, step size,
    scan (its kernels and the XLA ops round them) and gate, a gated
    memory unit's gate, an attention layer's relayout, kernels and join
    of each kind, and a matmul, named as the compiler named them in the
    cell's trace on the chip (my chip run, PR 40)."""
    fwd = "jit(step)/jvp(%s)/"
    again = "jit(step)/transpose(jvp(jvp()))/checkpoint/" \
        "rematted_computation/%s/"
    bwd = "jit(step)/transpose(jvp(jvp()))/checkpoint/%s/"
    kernel = lambda way, op, jit, name, kind="": (
        name, (way % op) + kind + "jit(%s)/%s/pallas_call:" % (jit, name))
    ops, at = [], 0.0
    for _ in range(2):
        for (kind, name), dur, is_kernel in (
                (("fusion", fwd % "ssm_conv.5" + "mul"), 7e-4, False),
                (("fusion", fwd % "ssm_dt.9" + "log1p"), 2e-4, False),
                (("fusion", fwd % "selective_scan.12" + "broadcast_in_dim"),
                 1e-4, False),
                (kernel(fwd, "selective_scan.12", "_fwd_pallas",
                        "selective_scan_fwd"), 15e-4, True),
                (("fusion", fwd % "ssm_gate.13" + "mul"), 3e-4, False),
                (("fusion", fwd % "gmu_gate.80" + "mul"), 2e-4, False),
                (("fusion", fwd % "diff_attention.30" + "window/select_n"),
                 1e-4, False),
                (kernel(fwd, "diff_attention.30", "_fwd_pallas", "flash_fwd",
                        "window/"), 4e-3, True),
                (kernel(fwd, "diff_attention.60", "_fwd_pallas", "flash_fwd",
                        "full/"), 13e-3, True),
                (kernel(fwd, "diff_attention.95", "_fwd_pallas", "flash_fwd",
                        "cross/"), 13e-3, True),
                (("fusion", fwd % "diff_attn.31" + "mul"), 6e-4, False),
                (kernel(again, "selective_scan.12", "_fwd_pallas",
                        "selective_scan_fwd"), 15e-4, True),
                (kernel(bwd, "selective_scan.12", "_bwd_pallas",
                        "selective_scan_bwd"), 4e-3, True),
                (("fusion", bwd % "selective_scan.12" + "reduce_sum"),
                 2e-4, False),
                (kernel(bwd, "diff_attention.30", "_bwd_pallas", "flash_bwd",
                        "window/"), 5e-3, True),
                (("fusion", bwd % "diff_attention.30" + "window/add_any"),
                 2e-4, False),
                (("fusion", bwd % "diff_attn.31" + "mul"), 8e-4, False),
                (("fusion", bwd % "ssm_conv.5" + "mul"), 9e-4, False),
                (("fusion", "jit(step)/jvp(mul.40)/dot_general"), 3e-3,
                 False)):
            ops.append(_op(kind, dur, name, at, is_kernel))
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
            "train": {"batch": 1, "seq_len": 8192, "tokens_per_step": 8192,
                      "counters": {}}}


def test_new_readers_on_a_window_by_hand(window, capsys):
    busy = window["trace"]["busy_s"]
    read = lambda name: cells.load_metric(name).read(window)
    arch = cells.load_arch("sambay")
    need = arch.scan_bytes_per_step(window["config"], 1, 8192)
    # both kernels, the forward's two runs both their time
    assert read("scan_hbm_roof_pct") == pytest.approx(
        100 * 2 * need / 819e9 / (2 * 7e-3), rel=1e-9)
    # conv 16e-4, gates 5e-4, the step size 2e-4 and the scan's XLA ops
    # 3e-4 a step; the kernels are not glue
    assert read("ssm_glue_dev_share_pct") == pytest.approx(
        100 * 2 * 26e-4 / busy, rel=1e-9)
    # the join 14e-4 and the relayout 3e-4 a step; the kernels are not
    assert read("diff_attn_glue_dev_share_pct") == pytest.approx(
        100 * 2 * 17e-4 / busy, rel=1e-9)
    said = capsys.readouterr().out
    assert "selective_scan_fwd 0.006000 s, selective_scan_bwd 0.008000 s" \
        in said
    assert "the scan kernels beside them 0.014000 s" in said
    assert "cross 0.026000 s, full 0.026000 s, window 0.018000 s" in said
    assert read("flash_roof_pct") == pytest.approx(
        100 * 2 * arith.flash_flops_per_step(window["config"], 1, 8192)
        / 197e12 / (2 * 35e-3), rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_a_program_without_the_layers(name):
    """In OPT's cell, or on a parent that has no such kernel and opens
    no such scope. The reader returns None and does not raise."""
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
