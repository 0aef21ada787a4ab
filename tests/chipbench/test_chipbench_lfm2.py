"""The cell ``lfm2_train_T32k`` (ISSUE 49): its files hold to their
source, the cell rehearses on the CPU through ``run.py`` (the flash
kernels in interpret mode, two heads of 32 ... four to a block in
groups of four), the program's logits and first loss lie under the
architecture's two limits against ``reference/lfm2_lm.py`` and the fp8
control's do not, each planted fault fails ``correct`` through the
driver, the arithmetic is the issue's, and the new reader reads a small
window written out by hand. (The guide's share test, four shares of 8
experts adding up to the uncut reference's routed layer, is
``tests/test_conv_moe.py``'s, beside the model's.)"""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import arith, cells, control, peaks, spans, tracing  # noqa: E402
from chipbench.drivers import train_steps                   # noqa: E402

CELL = "lfm2_train_T32k"
DEV = "/device:TPU:0"
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size"]
NEW = "short_conv_dev_share_pct"
CONV, FULL = "conv", "full_attention"


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
        if row["name"] == "LFM2-8B-A1B":
            assert cfg["published"] == row["config"]
            assert cfg["source"] == row["source_url"]
    assert cells.published_faults(cfg) == []
    assert cfg["reduced"] == REDUCED
    assert [cfg[key] for key in REDUCED] == [5, 1, 8, 8192]
    # every width as published, the list of kinds whole
    for key, value in cfg["published"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["conv_L_cache"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["rope_theta"], cfg["norm_eps"],
            cfg["published"]["num_experts"]) == (
                2048, 32, 8, 3, 7168, 1792, 4, 1000000, 1e-5, 32)
    assert "head_dim" not in cfg and "head_dim" not in cfg["published"]
    assert len(cfg["layer_types"]) == 24
    assert cfg["layer_types"][:5] == [CONV, CONV, FULL, CONV, CONV]
    # layer 0 dense, then one whole period at 3:1
    assert sorted(cfg["layer_types"][1:5]) == [CONV, CONV, CONV, FULL]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert (cfg["first_expert"], cfg["num_experts"]) == (0, 8)
    assert "4 chips" in cfg["deployment"] and "19 layers" in cfg["deployment"]
    for said in ("deployment", "parameters", "train_dtype"):
        assert cfg[said]
    assert "491.0 M" in cfg["parameters"]
    assert set(cfg["assumed"]) >= {
        "head_dim", "tie_word_embeddings", "conv", "attention", "router",
        "bias_update_rate", "num_experts", "first_expert", "seq_len",
        "embedding_init_std", "router_init_std"}
    tiny = {**cfg, **cfg["rehearse"]}
    assert {CONV, FULL} == set(tiny["layer_types"])
    assert tiny["num_dense_layers"] == 1
    # the rehearsal's heads: four of 32 to a block, all of one group
    from paddle_tpu.ops.flash_attention import heads_per_block
    group = tiny["num_attention_heads"] // tiny["num_key_value_heads"]
    g = heads_per_block(tiny["num_attention_heads"], tiny["head_dim"])
    assert (g, group) == (4, 4)
    assert heads_per_block(32, 64) == 2 and (32 // 8) % 2 == 0
    mix = cell["traffic_file"]
    assert (mix["driver"], mix["batch"], mix["seq_len"], mix["n_batches"],
            mix["warmup_steps"], mix["trace_steps"], mix["check_rows"]) == (
                "train_steps", 1, 32768, 4, 3, 6, 64)
    assert cell["chips"] == 1 and cell["traffic"] == "pretrain_T32k_b1"
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {NEW, "flash_roof_pct", "flash_fwd_roof_pct",
            "flash_bwd_roof_pct", "train_mfu_pct", "expert_matmul_roof_pct",
            "moe_glue_dev_share_pct", "expert_rows_max_over_mean",
            "device_idle_pct.train", "setup_enter_s", "xent_dev_share_pct",
            "optimizer_dev_share_pct", "unscoped_dev_share_pct",
            "step_interval_ms.train", "step_stall_pct.train",
            "exe_step_ms.train", "exe_self_ms.train", "step_host_ms.train",
            "setup_trace_lower_s.train", "setup_compile_s.train"} <= names
    # a routed model's expert FLOPs run outside ``mul``; and the two
    # lists that accepted tests pin to their first cells are left alone
    assert not {"matmul_roof_pct", "norm_rope_dev_share_pct",
                "moe_route_dev_share_pct", "hc_dev_share_pct"} & names
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NEW]
    reader = cells.load_metric(NEW)
    assert entry == {"name": NEW, "unit": reader.UNIT, "better": "lower",
                     "source": reader.SOURCE, "layer": reader.LAYER,
                     "moves": reader.MOVES, "workloads": [CELL]}
    (config,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == REDUCED and config["source"] == cfg["source"]


def test_arithmetic_is_the_issues():
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    arch = cells.load_arch("lfm2")
    d = 2048
    conv, attention = 4 * d * d, 2 * d * d + 2 * d * 512
    dense, expert = 3 * d * 7168, 3 * d * 1792
    assert [round(x / 1e4) for x in (conv, attention, dense, expert)] == [
        1678, 1049, 4404, 1101]
    # a token's held experts: top-4 times 8 of 32 = one expert
    touched = 4 * conv + attention + dense + 4 * (d * 32 + expert) + d * 8192
    assert arch.touched_parameters(cfg) == touched
    assert round(touched / 1e5) == 1827                     # 182.7 M
    assert arith.train_flops_per_token(cfg, 0) == 6 * touched
    # what this chip holds: 491.0 M parameters, 7.86 GB at 16 bytes
    held = (4 * (conv + 3 * d) + attention + 2 * 64 + 5 * 2 * d + dense
            + 4 * (d * 32 + 8 * expert) + 8192 * d + d)
    assert round(held / 1e5) == 4910 and round(held * 16 / 1e7) == 786
    t = 32768
    causal = t * (t + 1) // 2
    assert arch.useful_scores(t) == causal
    assert arith.flash_flops_per_step(cfg, 1, t) == 14 * 64 * 32 * causal
    assert arith.train_flops_per_token(cfg, t) == pytest.approx(
        6 * touched + arith.flash_flops_per_step(cfg, 1, t) / t)
    # the issue's reckoning: 51.3 TFLOP a step, 35.9 of matmuls and 15.4
    # of the one attention layer, 30% (18% at 16,384 rows)
    step = arith.train_flops_per_token(cfg, t) * t
    flash = arith.flash_flops_per_step(cfg, 1, t)
    assert [round(x / 1e11) for x in (step, 6 * touched * t, flash)] == [
        513, 359, 154]
    assert round(100 * flash / step) == 30
    half = arith.flash_flops_per_step(cfg, 1, t // 2)
    assert round(100 * half / (6 * touched * t // 2 + half)) == 18
    assert arch.expert_flops_per_pair(cfg) == 18 * d * 1792
    assert arith.matmul_scopes(cfg) == ("mul",)
    # four conv layers, 15 C bf16 values a row: 8.05 GB, 9.8 ms at 819 GB/s
    assert arch.short_conv_bytes_per_step(cfg, 1, t) == 4 * t * 15 * d * 2
    assert round(arch.short_conv_bytes_per_step(cfg, 1, t) / 819e9 * 1e4) \
        == 98


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


def test_the_program_lies_under_its_limits_and_the_control_does_not():
    """``control.py``'s readings at the rehearsal's size: the program's
    bf16-AMP logits against the float32 reference handed its choices
    under ``TRAIN_LOGITS_RTOL``, the fp8 control handed the same over
    it, by three times the program's. With the table drawn as the
    cell draws it, for logits of unit scale (64^-0.5 at this width):
    the rehearsal's own 1.0 starts the stream at an exact embedding
    that outweighs what any arithmetic adds to it, fp8's too."""
    arch = cells.load_arch("lfm2")
    cell = _tiny_cell()
    cell["config_file"]["embedding_init_std"] = 64 ** -0.5
    program, fp8, routed = control.readings(cell, 13, False)
    assert routed
    assert program <= arch.TRAIN_LOGITS_RTOL < fp8
    assert fp8 >= 3 * program


@pytest.mark.parametrize("fault", [
    "sound", "the_gates_change_places", "an_activation_after_the_taps",
    "the_taps_reversed", "no_qk_norm_weight_no_rotation",
    "softmax_scores_for_sigmoid", "the_four_weights_not_normalised",
    "query_head_j_reads_key_head_j_mod_2"])
def test_a_planted_fault_fails_correct(monkeypatch, fault):
    """The whole driver at the rehearsal's size. A program whose
    convolution operator gates by C going in and B coming out, puts a
    SiLU behind the taps or runs them back to front, leaves q and k
    unturned, scores by softmax, leaves the chosen weights as the
    sigmoids gave them, or maps query heads to key/value heads by the
    remainder parts from the reference by more than a limit, and
    ``correct`` comes out false."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.models import conv_moe as model
    from paddle_tpu.ops import causal_attention as CA
    from paddle_tpu.ops import short_conv as SC
    whole, routed = model.conv_moe_lm, fluid.layers.routed_experts
    with_kw = lambda fn, **fixed: lambda *a, **kw: fn(*a, **{**kw, **fixed})
    sound_conv, sound_taps = SC.gated_short_conv, SC.causal_taps
    if fault == "the_gates_change_places":
        def swapped(x, w):
            c = x.shape[-1] // 3
            return sound_conv(jnp.concatenate(
                [x[..., c:2 * c], x[..., :c], x[..., 2 * c:]], -1), w)
        monkeypatch.setattr(SC, "gated_short_conv", swapped)
    if fault == "an_activation_after_the_taps":
        monkeypatch.setattr(SC, "causal_taps", lambda *a: jax.nn.silu(
            sound_taps(*a)))
    if fault == "the_taps_reversed":
        monkeypatch.setattr(SC, "causal_taps", lambda x, w, *a: sound_taps(
            x, w[::-1], *a))
    if fault == "no_qk_norm_weight_no_rotation":
        sound = fluid.layers.qk_norm_rope
        monkeypatch.setattr(fluid.layers, "qk_norm_rope",
                            with_kw(sound, rotate=False))
    if fault == "softmax_scores_for_sigmoid":
        monkeypatch.setattr(fluid.layers, "routed_experts",
                            with_kw(routed, score_func="softmax",
                                    shared_expert=True))
    if fault == "the_four_weights_not_normalised":
        monkeypatch.setattr(model, "conv_moe_lm",
                            with_kw(whole, norm_topk=False))
    if fault == "query_head_j_reads_key_head_j_mod_2":
        # (the wrong mapping as PR 46's test wrote it out)
        from test_chipbench_smallthinker import _heads_read_j_mod
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
    # three routed layers, 4 x 512 rows, top-2, once a step
    assert sum(counters["expert_rows"]) == steps * 3 * 2048 * 2
    assert len(counters["selection_bias_abs_max"]) == 3
    assert 0 < max(counters["selection_bias_abs_max"]) <= steps * 1e-3 + 1e-9


# -- the new reader on a window written out by hand ---------------------------

def _op(kind, dur, op_name, start, kernel=False):
    text = "%%%s.1 = f32[8]{0} %s(...)" % (
        kind, "custom-call(...), custom_call_target=\"tpu_custom_call\""
        if kernel else "fusion")
    return spans.device_op(text, start, dur, op_name)


@pytest.fixture()
def window():
    """Two traced steps of a program ``step`` whose layers are
    recompute regions: per step a conv layer's operator, forward,
    recomputed and backward, beside its two projections, the attention
    layer's QK-norm and flash kernels and an expert layer's glue, named
    as the compiler named them in the cell's trace on the chip (my chip
    run, PR 49)."""
    fwd = "jit(step)/jvp(%s)/"
    again = "jit(step)/transpose(jvp(jvp()))/checkpoint/" \
        "rematted_computation/%s/"
    bwd = "jit(step)/transpose(jvp(jvp()))/checkpoint/%s/"
    conv = "gated_short_conv.12"
    ops, at = [], 0.0
    for _ in range(2):
        for (kind, name), dur, kernel in (
                (("fusion", fwd % "mul.11" + "dot_general:"), 4e-3, False),
                (("fusion", fwd % conv + "mul:;" + fwd % conv + "add:"),
                 9e-4, False),
                (("fusion", fwd % "mul.13" + "dot_general:"), 2e-3, False),
                (("fusion", fwd % "qk_norm_rope.30" + "mul:"), 3e-3, False),
                (("flash_fwd", fwd % "causal_attention.33" + "full/"
                  "jit(_fwd_pallas)/flash_fwd/pallas_call:"), 60e-3, True),
                (("fusion", again % conv + "mul:"), 9e-4, False),
                (("flash_bwd", bwd % "causal_attention.33" + "full/"
                  "jit(_bwd_pallas)/flash_bwd/pallas_call:"), 120e-3, True),
                (("convert_multiply_fusion", bwd % conv + "mul:"), 1.1e-3,
                 False),
                (("multiply_add_fusion", bwd % conv + "pad:;" + bwd % conv
                  + "add_any:"), 8e-4, False),
                (("fusion", bwd % "routed_experts.20" + "while/body/add:"),
                 2e-3, False)):
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
            "train": {"batch": 1, "seq_len": 32768,
                      "tokens_per_step": 32768,
                      "counters": {"expert_rows": [4096] * 8 + [5] * 24,
                                   "steps": [1]}}}


def test_the_new_reader_on_a_window_by_hand(window, capsys):
    busy = window["trace"]["busy_s"]
    read = lambda name: cells.load_metric(name).read(window)
    conv = 2 * (9e-4 + 9e-4 + 1.1e-3 + 8e-4)
    assert read(NEW) == pytest.approx(100 * conv / busy, rel=1e-9)
    said = capsys.readouterr().out
    assert ("fusion 0.003600, convert_multiply_fusion 0.002200, "
            "multiply_add_fusion 0.001600" in said)
    # its neighbours read their own ops and none of its
    assert read("moe_glue_dev_share_pct") == pytest.approx(
        100 * 2 * 2e-3 / busy, rel=1e-9)
    assert read("norm_rope_dev_share_pct") == pytest.approx(
        100 * 2 * 3e-3 / busy, rel=1e-9)
    peak = window["peaks"]["flops_bf16"]
    flash = arith.flash_flops_per_step(window["config"], 1, 32768)
    assert read("flash_roof_pct") == pytest.approx(
        100 * 2 * flash / peak / (2 * 180e-3), rel=1e-9)
    # the forward kernel runs once a step (a region keeps its output):
    # 2/7 and 5/7 of the FLOPs over each kernel's own time
    assert read("flash_fwd_roof_pct") == pytest.approx(
        100 * 2 * flash * 2 / 7 / peak / (2 * 60e-3), rel=1e-9)
    assert read("flash_bwd_roof_pct") == pytest.approx(
        100 * 2 * flash * 5 / 7 / peak / (2 * 120e-3), rel=1e-9)
    assert read("expert_rows_max_over_mean") == pytest.approx(1.0)


def test_the_new_reader_finds_nothing_in_a_program_without_the_op():
    """In Phi-4-mini-flash's cell, whose convolution is the op
    ``ssm_conv``, on this PR's parent as on its change. The reader
    returns None and does not raise."""
    cfg = cells.load_cell(ROOT, "phi4flash_train_T8k")["config_file"]
    ops = [_op("fusion", 1e-3, "jit(step)/jvp(mul.3)/dot_general:", 0.0),
           _op("fusion", 1e-3, "jit(step)/jvp(ssm_conv.4)/mul:", 1e-3)]
    modules = [{"program": "step", "start": 0.0, "dur": 2e-3}]
    run = {"trace": {"busy_s": 2e-3, "window_s": 2e-3},
           "spans": {"host": [], "ops": ops, "modules": modules,
                     "compiles": None},
           "config": cfg, "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite"),
           "train": {"batch": 1, "seq_len": 8192, "tokens_per_step": 8192,
                     "counters": {}}}
    assert cells.load_metric(NEW).read(run) is None
    run.pop("trace")                   # an untraced run
    run["spans"] = None
    assert cells.load_metric(NEW).read(run) is None


# -- an accepted test that pins the benchmark's END --------------------------

def test_smallthinkers_files_hold_to_their_source_as_pr_46_left_them(
        monkeypatch):
    """``test_chipbench_smallthinker.py``'s first test asserts that PR
    46's cell and two metrics are the LAST entries of ``BENCHMARK.json``,
    which stopped being so when this cell was appended, as it had to be;
    the file is the benchmark's, and no PR but a ``benchmark`` PR may
    edit it (``tests/conftest.py`` says where it is marked). Everything
    else it asserts still has to hold: here it runs against the
    benchmark cut back to what PR 46 appended last."""
    import test_chipbench_smallthinker as theirs
    sound = cells.load_json

    def as_pr_46_left_it(path):
        bench = sound(path)
        if os.path.basename(path) != "BENCHMARK.json":
            return bench
        cut = lambda entries, last: entries[:1 + max(
            i for i, e in enumerate(entries) if e["name"] == last)]
        return {**bench,
                "workloads": cut(bench["workloads"], theirs.CELL),
                "per_layer": cut(bench["per_layer"], theirs.NEW[1])}
    monkeypatch.setattr(cells, "load_json", as_pr_46_left_it)
    theirs.test_the_configuration_holds_to_its_source()
