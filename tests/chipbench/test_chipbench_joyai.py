"""The cell ``joyai_train_T8k`` (ISSUE 55): its files hold to their
source and the built program counts the parameters the file states, the
arithmetic counts the module, the cell rehearses on the CPU through
``run.py``, Xing's program is op for op what the model file built
before it learnt the module, and the two new readers read a small
window written out by hand (and nothing on a tree without the module).
The planted faults are ``test_chipbench_joyai_faults.py``'s: a file is
one worker's, and together they would hold it over two minutes."""

import hashlib
import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import arith, cells, oplog, spans            # noqa: E402

CELL = "joyai_train_T8k"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW = ("mtp_dev_share_pct", "mtp_loss_over_main")


def _tiny_cell():
    """The cell cut to its rehearsal size, as ``run.load_cell`` cuts it
    (without steering the kernels: the dense path on the CPU)."""
    cell = cells.load_cell(ROOT, CELL)
    for part in ("config_file", "traffic_file"):
        cell[part] = {**cell[part], **cell[part].get("rehearse", {})}
    return cell


def _built(cfg, seq):
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        cells.load_arch(cfg["arch"]).build(cfg, seq)
    return main


def test_the_configuration_holds_to_its_source():
    cell = cells.load_cell(ROOT, CELL)
    cfg = cell["config_file"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = []
    if os.path.exists(catalog):         # the guides' catalog, where it is
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if line.strip()]
    for row in rows:
        if row["name"] == "JoyAI-LLM-Flash":
            assert cfg["published"] == row["config"]
            assert cfg["source"] == row["source_url"]
    assert cells.published_faults(cfg) == []
    assert cfg["reduced"] == REDUCED
    assert [cfg[key] for key in REDUCED] == [5, 16, 16160]
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    for key, value in cfg["published"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    # the module stays, on a plain stream, with the published rotary
    assert (cfg["num_nextn_predict_layers"], cfg["rope_interleave"],
            cfg["rope_scaling"], cfg["rope_theta"], cfg["n_group"]) == (
                1, True, None, 32000000, 1)
    assert "hc_mult" not in cfg
    assert cfg["num_experts"] == cfg["n_routed_experts"]
    assert (cfg["mtp_loss_weight"], cfg["first_expert"]) == (0.3, 0)
    for said in ("deployment", "assumed", "parameters", "train_dtype"):
        assert cfg[said]
    for key in ("mtp_loss_weight", "mtp_input_order", "mtp_hidden",
                "mtp_shared_head", "rope", "head_dim", "bias_update_rate"):
        assert cfg["assumed"][key], key
    mix = cell["traffic_file"]
    assert (mix["batch"], mix["seq_len"], mix["n_batches"],
            mix["check_rows"]) == (1, 8192, 4, 64)
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {
        "mla_flash_fwd_roof_pct", "mla_flash_bwd_roof_pct",
        "mla_glue_dev_share_pct", "flash_roof_pct", "train_mfu_pct",
        "expert_matmul_roof_pct", "moe_glue_dev_share_pct",
        "expert_rows_max_over_mean", "dense_matmul_roof_pct",
        "dense_matmul_fwd_roof_pct", "dense_matmul_bwd_roof_pct",
        "second_forward_dev_share_pct", "xent_dev_share_pct",
        "optimizer_dev_share_pct", "unscoped_dev_share_pct",
        "setup_enter_s"} <= names
    assert not {"hc_dev_share_pct", "flash_fwd_roof_pct",
                "flash_bwd_roof_pct", "matmul_roof_pct",
                "norm_rope_dev_share_pct"} & names


def test_the_built_program_counts_the_parameters_the_file_states():
    """The program at the cell's own size, built and not run: 680.4 M
    parameters, 10.9 GB at 16 bytes each, module by module as the
    file's ``parameters`` line says."""
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    main = _built(cfg, 8192)
    sizes = {p.name: math.prod(p.shape)
             for p in main.global_block().all_parameters()}
    of = lambda part: sum(n for name, n in sizes.items() if part in name)
    attention = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
                 + 4096 * 2048 + 2 * 2048 + 1536 + 512)
    expert = 3 * 2048 * 768
    routed = attention + expert + 2048 * 256 + 16 * expert
    assert round(attention / 1e4) == 2635                   # 26.35 M
    assert of("joyai_l0_") == attention + 3 * 2048 * 7168   # 70.4 M
    for i in range(1, 5):
        assert of("joyai_l%d_" % i) == routed               # 107.1 M
    assert of("joyai_mtp_") == routed + 4096 * 2048 + 3 * 2048   # 115.5 M
    assert sizes["joyai_word_emb"] == sizes["joyai_head"] == 16160 * 2048
    total = sum(sizes.values())
    assert total == 680439808
    assert abs(total / 680e6 - 1) < 0.01
    assert "680.4 M" in cfg["parameters"] and "10.9 GB" in cfg["parameters"]


def test_arithmetic_is_the_issues_with_six_blocks():
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    arch = cells.load_arch("joyai")
    attention = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
                 + 4096 * 2048)
    expert, head = 3 * 2048 * 768, 2048 * 16160
    routed = expert + 2048 * 256 + 0.5 * expert    # 8 x 16 / 256 held pairs
    module = 2 * 2048 * 2048 + attention + routed + head
    assert arch.module_parameters(cfg) == module
    touched = (5 * attention + 3 * 2048 * 7168 + 4 * routed + head + module)
    assert arch.touched_parameters(cfg) == touched
    assert round(touched / 1e5) == 3147
    assert arith.train_flops_per_token(cfg, 0) == 6 * touched
    scores = 4096 * 2 * (320 + 832) * 32             # a block's, a token
    assert arith.train_flops_per_token(cfg, 8192) == 6 * touched + 6 * scores
    # 1.89 + 1.81 GFLOP a token, 30.3 TFLOP a step of 8,192 (the issue's
    # 27.8 counts five blocks' scores, and six as it says elsewhere)
    assert round(arith.train_flops_per_token(cfg, 8192) * 8192 / 1e11) == 303
    assert round((6 * touched + 5 * scores) * 8192 / 1e11) == 278
    # the module: a fifth of the step; the held experts' pairs 2%
    share = (6 * module + scores) / arith.train_flops_per_token(cfg, 8192)
    assert 0.20 < share < 0.21
    assert 0.018 < 6 * 5 * 0.5 * expert / (6 * touched + 6 * scores) < 0.02
    assert arith.flash_flops_per_step(cfg, 1, 8192) \
        == 8192 ** 2 // 2 * 2 * (320 + 832) * 32 * 6
    assert arch.flash_flops_split(cfg) == (320 / 1152, 832 / 1152)
    assert arch.expert_flops_per_pair(cfg) == 18 * 2048 * 768
    assert arith.matmul_scopes(cfg) == ("mul",)


@pytest.mark.parametrize("seed", ["3000000029", "2200000013"])
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


# -- Xing's program, which the model file also builds --------------------------

# of the parent of PR 55 (commit b50f6c4), at the rehearsal's size and at
# the cell's: how many ops, sha256 of their types in order, how many
# parameters, sha256 of their sorted (name, shape)
XING = {"rehearse": (64, 83, "fafe00bec09afab4", 74, "159e1d5bdc3d159a"),
        "whole": (4096, 131, "2c7ebb20995dc9e4", 124, "90393643688173fe")}


@pytest.mark.parametrize("size", sorted(XING))
def test_xings_program_is_op_for_op_the_parents(size):
    seq, n_ops, types_hash, n_params, params_hash = XING[size]
    cfg = cells.load_cell(ROOT, "xing4_train_T4k")["config_file"]
    if size == "rehearse":
        cfg = {**cfg, **cfg["rehearse"]}
    main = _built(cfg, seq)

    def walk(block):
        for op in block.ops:
            yield op
            if op.type == "recompute_block":
                yield from walk(op.attr("sub_block"))

    ops = list(walk(main.global_block()))
    digest = lambda x: hashlib.sha256(
        json.dumps(x).encode()).hexdigest()[:16]
    params = sorted((p.name, tuple(p.shape))
                    for p in main.global_block().all_parameters())
    assert (len(ops), digest([op.type for op in ops])) == (n_ops, types_hash)
    assert (len(params), digest(params)) == (n_params, params_hash)
    # no module, no sums: what JoyAI's configuration asks for builds
    # nothing here
    assert {op.attr("module") for op in ops} == {None}
    assert not [op for op in ops if op.type == "step_sum"]
    assert sum(op.type == "hyper_connection" for op in ops) > 0


# -- an accepted test that pins the benchmark's lists --------------------------

def test_pr_51s_pinned_entries_are_as_their_pr_left_them(monkeypatch):
    """``test_chipbench_oplog.py`` asserts that PR 51's four metrics are
    the LAST entries of ``per_layer`` and that their lists are every
    cell's; PR 53 appended a cell and two metrics and ran the pin
    against the benchmark less its own (``test_chipbench_olmo_hybrid.py``,
    which this PR may not edit either); this PR appends a cell and two
    metrics more, so that copy now fails too and is marked where the pin
    is (``tests/conftest.py``). Here the pin runs against the benchmark
    with what BOTH PRs appended taken off. What a stripped copy cannot
    see is asserted first: this cell IS on those four lists. (A
    ``benchmark`` PR should make the pinned test read the cells off
    ``BENCHMARK.json`` and drop both copies with the markers.)"""
    import test_chipbench_oplog as theirs
    sound = json.load
    lists = {m["name"]: m.get("workloads", ())
             for m in cells.load_json(
                 os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]}
    for name in theirs.NAMES:
        assert CELL in lists[name], name
    later_cells = ("olmohybrid_train_T8k", CELL)
    later_metrics = ("delta_rule_dev_share_pct",
                     "delta_glue_dev_share_pct") + NEW

    def as_pr_51_left_it(f):
        bench = sound(f)
        if not (isinstance(bench, dict) and "per_layer" in bench):
            return bench
        without = lambda m: {**m, "workloads": [
            w for w in m["workloads"] if w not in later_cells]} \
            if "workloads" in m else m
        return {**bench,
                "workloads": [w for w in bench["workloads"]
                              if w["name"] not in later_cells],
                "end_to_end": [without(m) for m in bench["end_to_end"]],
                "per_layer": [without(m) for m in bench["per_layer"]
                              if m["name"] not in later_metrics]}
    monkeypatch.setattr(theirs.json, "load", as_pr_51_left_it)
    theirs.test_the_entries_in_benchmark_json()


# -- the two new readers on a window written out by hand -----------------------

PEAK, STEPS, BUSY = 197e12, 2, 0.5
FWD, AGAIN, BWD = ("jit(step)/jvp()/checkpoint/",
                   "jit(step)/transpose(jvp())/checkpoint/"
                   "rematted_computation/",
                   "jit(step)/transpose(jvp())/checkpoint/")
# (op_name, seconds in each traced step)
OPS = [
    # the main stack: a block's attention and the head
    (FWD + "mla_attention.9/jit(_fwd_pallas2)/flash_fwd", 0.010),
    ("jit(step)/jvp(mul.40)/dot_general:", 0.008),
    ("jit(step)/jvp(softmax_with_cross_entropy.42)/reduce:", 0.002),
    # the module: the table again, eh_proj, its block three times over,
    # the head and the loss a second time
    ("jit(step)/jvp(lookup_table.50)/gather:", 0.0005),
    ("jit(step)/jvp(mul.55)/dot_general:", 0.001),
    (FWD + "mla_attention.60/jit(_fwd_pallas2)/flash_fwd", 0.010),
    (AGAIN + "mla_attention.60/jit(_fwd_pallas2)/flash_fwd", 0.010),
    (BWD + "mla_attention.60/jit(_bwd_pallas2)/flash_bwd", 0.025),
    (BWD + "mul.62/dot_general:", 0.003),
    ("jit(step)/jvp(mul.70)/dot_general:", 0.008),
    ("jit(step)/transpose(jvp(mul.70))/dot_general:", 0.016),
    ("jit(step)/jvp(softmax_with_cross_entropy.72)/reduce:", 0.002),
    ("jit(step)/step_sum.75/add:", 0.0001),
    # the module's held experts' kernels carry no scope
    ("jit(step)/while/body/ragged-dot:", 0.004),
    ("jit(step)/adam.300/mul:", 0.001)]
MODULE_S = (0.0005 + 0.001 + 0.010 + 0.010 + 0.025 + 0.003 + 0.008 + 0.016
            + 0.002 + 0.0001)


def _rows(with_module=True):
    row = lambda seq, kind, module, region=None, weights=(): dict(
        {"seq": seq, "type": kind, "weights": weights, "region": region,
         "kept": None, "inputs": {}, "outputs": {}},
        **({"module": module} if with_module else {}))
    mul = lambda seq, module, weight, region=None: dict(
        row(seq, "mul", module, region, (weight,)), mkn=(8192, 2048, 2048),
        grads=("x", "w"), operand_dtype="bfloat16")
    return [row(9, "mla_attention", None, 4), mul(40, None, "joyai_head"),
            row(42, "softmax_with_cross_entropy", None),
            row(50, "lookup_table", "mtp", None, ("joyai_word_emb",)),
            mul(55, "mtp", "joyai_mtp_eh_proj"),
            row(60, "mla_attention", "mtp", 5),
            mul(62, "mtp", "joyai_mtp_o", 5), mul(70, "mtp", "joyai_head"),
            row(72, "softmax_with_cross_entropy", "mtp"),
            row(75, "step_sum", "mtp")]


def _run(cfg, counters):
    window, t = {"host": [], "compiles": None, "ops": [], "modules": []}, 0.0
    for _ in range(STEPS):
        t0 = t
        for i, (name, dur) in enumerate(OPS):
            window["ops"].append(spans.device_op("fusion.%d" % i, t, dur,
                                                 name))
            t += dur
        window["modules"].append({"program": "step", "start": t0,
                                  "dur": t - t0})
    return {"trace": {"busy_s": BUSY}, "spans": window, "config": cfg,
            "peaks": {"flops_bf16": PEAK},
            "train": {"counters": counters}}


def _plant_ledger(monkeypatch, rows):
    def read(root=None, backward=None):
        return ({"root": root, "backward": backward, "step": 3,
                 "t_build": 1.0, "count": len(rows)},
                [dict(r) for r in rows])
    monkeypatch.setattr(oplog, "ledger", lambda: read)


def test_the_new_readers_on_a_window_by_hand(monkeypatch, capsys):
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    _plant_ledger(monkeypatch, _rows())
    run = _run(cfg, {"main_loss": [9.7 * 40], "mtp_loss": [9.9 * 40],
                     "steps": [40]})
    read = lambda name: cells.load_metric(name).read(run)
    assert read("mtp_dev_share_pct") == pytest.approx(
        100.0 * STEPS * MODULE_S / BUSY)
    assert read("mtp_loss_over_main") == pytest.approx(9.9 / 9.7)
    out = capsys.readouterr().out
    assert "mtp_dev_share_pct: 75.600 ms a step in the module's ops: " \
        "block 48.000, eh_proj 1.000, head 24.000, loss 2.100, rest 0.500" \
        in out


def test_the_new_readers_find_nothing_without_the_module(monkeypatch):
    """On the parent of PR 55 the ledger's rows state no ``module`` and
    the architecture counts no loss; in another cell the architecture
    names no module; a module that fell out of the cost sums nothing.
    The readers return None and do not raise."""
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    read = lambda name, run: cells.load_metric(name).read(run)
    _plant_ledger(monkeypatch, _rows(with_module=False))
    assert read("mtp_dev_share_pct", _run(cfg, {})) is None
    assert read("mtp_loss_over_main", _run(cfg, {})) is None
    assert read("mtp_loss_over_main", _run(cfg, {
        "main_loss": [388.0], "mtp_loss": [0.0]})) is None
    assert read("mtp_loss_over_main", _run(cfg, {
        "main_loss": [388.0]})) is None
    _plant_ledger(monkeypatch, _rows())
    xing = cells.load_cell(ROOT, "xing4_train_T4k")["config_file"]
    assert read("mtp_dev_share_pct", _run(xing, {})) is None
    for run in (_run(cfg, {}), _run(cfg, {})):
        run["spans"], run["trace"] = None, None        # an untraced run
        for name in NEW:
            assert read(name, run) is None
    # no ledger at all
    monkeypatch.setattr(oplog, "ledger", lambda: None)
    assert read("mtp_dev_share_pct", _run(cfg, {})) is None
