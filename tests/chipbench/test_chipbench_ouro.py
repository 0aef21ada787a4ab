"""The cell ``ouro_train_T8k`` (ISSUE 59): the configuration holds to
its source, the built program counts the parameters the file states,
the arithmetic counts every visit, the cell rehearses through
``run.py``, the fp8 control fails, the four new readers on a window
written out by hand, and the entries in ``BENCHMARK.json`` (read off
the file: nothing here pins the END of a list)."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import arith, cells, oplog, spans            # noqa: E402
from chipbench.reference import compare                     # noqa: E402

CELL = "ouro_train_T8k"
REDUCED = ["num_hidden_layers", "layer_types"]
NEW = ("loop_head_dev_share_pct", "exit_dev_share_pct", "exit_step_mean",
       "loop_loss_last_over_first")
# the accepted metrics' lists the cell is on (ISSUE 59, item 8)
LISTS = ("tokens_per_s", "flash_roof_pct", "flash_fwd_roof_pct",
         "flash_bwd_roof_pct", "matmul_roof_pct", "dense_matmul_roof_pct",
         "dense_matmul_fwd_roof_pct", "dense_matmul_bwd_roof_pct",
         "step_host_ms.train", "train_mfu_pct", "device_idle_pct.train",
         "optimizer_dev_share_pct", "unscoped_dev_share_pct",
         "exe_self_ms.train", "setup_trace_lower_s.train",
         "setup_compile_s.train", "step_interval_ms.train",
         "step_stall_pct.train", "exe_step_ms.train",
         "second_forward_dev_share_pct")


def _tiny_cell():
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
        if row["name"] == "Ouro-2.6B":
            assert cfg["published"] == row["config"]
            assert cfg["source"] == row["source_url"]
    assert cells.published_faults(cfg) == []
    assert cfg["reduced"] == REDUCED
    assert (cfg["num_hidden_layers"], cfg["layer_types"]) == (
        8, ["full_attention"] * 8)
    assert cfg["published"]["num_hidden_layers"] == 48
    for key, value in cfg["published"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    # every width, all 16 heads, the whole vocabulary, all four visits
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["vocab_size"], cfg["total_ut_steps"], cfg["rope_theta"],
            cfg["max_window_layers"]) == (2048, 5632, 128, 16, 16, 49152,
                                          4, 1000000, 48)
    assert (cfg["arch"], cfg["entropy_weight"]) == ("ouro", 0.1)
    for said in ("deployment", "assumed", "parameters", "train_dtype"):
        assert cfg[said]
    for key in ("objective", "entropy_weight", "norms", "bias",
                "attention", "initialisers", "seq_len"):
        assert cfg["assumed"][key], key
    mix = cell["traffic_file"]
    assert (mix["batch"], mix["seq_len"], mix["n_batches"],
            mix["check_rows"]) == (1, 8192, 4, 64)


def test_the_entries_in_benchmark_json():
    """One configuration, one cell, four metrics, read off the file by
    name; the cell's name on the lists of the accepted metrics it
    reports and on no other; every new metric lists this cell alone and
    moves ``tokens_per_s``."""
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    (config,) = [c for c in bench["configs"]
                 if c["name"] == "ouro-2.6b-train"]
    assert config["file"] == "chipbench/configs/ouro-2.6b-train.json"
    assert config["reduced"] == REDUCED
    assert config["source"] == cells.load_json(
        os.path.join(ROOT, config["file"]))["source"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b-train", "pretrain_T8k_b1", 1)
    for entry in (config, cell):
        assert 0 < len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    on = {name for name, m in by_name.items() if CELL in m.get(
        "workloads", ())}
    assert on == set(LISTS) | set(NEW)
    for name in NEW:
        m = by_name[name]
        assert (m["workloads"], m["moves"]) == ([CELL], "tokens_per_s")
        reader = cells.load_metric(name)
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == (
            reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES)
    loaded = cells.load_cell(ROOT, CELL)
    assert {m["name"] for m in loaded["end_to_end"]} == {"tokens_per_s",
                                                         "setup_s"}
    # the loss's and the norms' accepted readers do not read this cell
    # (PERF.md section 7 says why)
    assert not {"xent_dev_share_pct", "norm_rope_dev_share_pct"} & on


def test_the_built_program_counts_the_parameters_the_file_states():
    """The program at the cell's own size, built and not run: 612.4 M
    parameters, ONE set for the four visits, and the stack's ops
    once."""
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    main = _built(cfg, 8192)
    sizes = {p.name: math.prod(p.shape)
             for p in main.global_block().all_parameters()}
    of = lambda part: sum(n for name, n in sizes.items() if part in name)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    for i in range(8):
        assert of("ouro_l%d_" % i) == layer                 # 51.39 M
    assert sizes["ouro_word_emb"] == sizes["ouro_head"] == 49152 * 2048
    assert (sizes["ouro_gate_w"], sizes["ouro_gate_b"],
            sizes["ouro_final_norm"]) == (2048, 1, 2048)
    total = sum(sizes.values())
    assert total == 8 * layer + 2 * 49152 * 2048 + 4097 == 612438017
    assert "612.4 M" in cfg["parameters"] and "7.35 GB" in cfg["parameters"]
    arch = cells.load_arch("ouro")
    assert arch.program_visits(main) == 4
    (loop,) = [o for o in main.global_block().ops if o.type == "repeat"]
    regions = [o for o in loop.attr("sub_block").ops
               if o.type == "recompute_block"]
    assert len(regions) == 8 + 1
    assert sum(m.type == "mul" for r in regions
               for m in r.attr("sub_block").ops) == 8 * 7 + 1


def test_arithmetic_counts_every_visit():
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    arch = cells.load_arch("ouro")
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    visit = 8 * layer + 2048 * 49152 + 2048
    assert arch.visit_parameters(cfg) == visit
    assert arith.train_flops_per_token(cfg, 0) == 4 * 6 * visit
    scores = 4096 * 14 * 16 * 128 * 8          # a visit's, a token
    assert arith.train_flops_per_token(cfg, 8192) == 4 * (6 * visit + scores)
    # 12.3 GFLOP a token in the matmuls and 3.8 in attention: 131 TFLOP
    # a step of 8,192 tokens, the heads a fifth of the matmuls
    assert round(4 * 6 * visit / 1e8) == 123
    assert round(4 * scores / 1e8) == 38
    assert round(arith.train_flops_per_token(cfg, 8192) * 8192 / 1e12) == 131
    assert 0.19 < 2048 * 49152 / visit < 0.20
    assert arith.flash_flops_per_step(cfg, 1, 8192) \
        == 7 * 8192 ** 2 * 16 * 128 * 8 * 4
    assert arith.matmul_scopes(cfg) == ("mul",)
    # a decode step reads the stack once a visit
    small = arch.decode_step_bytes(cfg, 2, 0, 1)
    assert small > 4 * 2 * (8 * layer + 2048 * 49152)


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


def test_the_fp8_control_fails_the_logits_limit():
    """The reference in fp8 e4m3 operands against itself in float32, at
    the rehearsal's widths and four visits of two layers: over
    ``TRAIN_LOGITS_RTOL`` in the last visit's logits AND, alone, in the
    four ``log p_t`` (the gates read streams that fp8 has moved)."""
    import jax
    import jax.numpy as jnp
    arch = cells.load_arch("ouro")
    cfg = _tiny_cell()["config_file"]
    rng = np.random.RandomState(0)
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    w = lambda *shape: (rng.randn(*shape) * shape[0] ** -0.5).astype(
        np.float32)
    one = lambda: {"ln1": np.ones(d, np.float32),
                   "ln1_post": np.ones(d, np.float32),
                   "ln2": np.ones(d, np.float32),
                   "ln2_post": np.ones(d, np.float32), "wq": w(d, hd),
                   "wk": w(d, hd), "wv": w(d, hd), "wo": w(hd, d),
                   "gate": w(d, f), "up": w(d, f), "down": w(f, d)}
    params = {"word_emb": w(v, d) * v ** 0.5 * 0.02,
              "final_norm": np.ones(d, np.float32), "w_out": w(d, v),
              "gate_w": w(d, 1), "gate_b": np.zeros(1, np.float32),
              "layers": [one(), one()]}
    tokens = jnp.asarray(rng.randint(0, v, 128))
    ref = np.asarray(jax.jit(arch.logits_at, static_argnums=(2, 3, 4))(
        params, tokens, 96, 32, _Frozen(cfg)))
    low = np.asarray(jax.jit(arch.control_logits_at, static_argnums=(
        2, 3, 4))(params, tokens, 96, 32, _Frozen(cfg)))
    assert ref.shape == low.shape == (32, v + 4)
    assert compare.logits_error(low, ref) > arch.TRAIN_LOGITS_RTOL
    assert compare.logits_error(low[:, :v], ref[:, :v]) \
        > arch.TRAIN_LOGITS_RTOL
    assert np.abs(low[:, v:] - ref[:, v:]).max() > 1e-3
    np.testing.assert_allclose(np.exp(ref[:, v:]).sum(-1), 1.0, atol=1e-5)


class _Frozen(dict):
    """A configuration as a static argument of a jitted reference."""
    def __hash__(self):
        return id(self)


# -- an accepted test that pins the benchmark's lists --------------------------

def test_pr_51s_pinned_entries_are_as_their_pr_left_them(monkeypatch):
    """``test_chipbench_oplog.py`` asserts that PR 51's four metrics are
    the LAST entries of ``per_layer`` and that their lists are every
    cell's. PRs 53 and 55 each appended a cell and two metrics and ran
    the pin against the benchmark less their own
    (``test_chipbench_olmo_hybrid.py``, ``test_chipbench_joyai.py``,
    which this PR may not edit either); this PR appends a cell and four
    metrics more, so PR 55's copy now fails too and is marked where the
    pin is (``tests/conftest.py``). Here the pin runs against the
    benchmark with what ALL THREE PRs appended taken off, the later
    cells and metrics read off ``BENCHMARK.json`` by what follows PR
    51's. What a stripped copy cannot see is asserted first: this cell
    IS on those four lists. (A ``benchmark`` PR should make the pinned
    test read the cells off ``BENCHMARK.json`` and drop the three
    copies with the markers.)"""
    import test_chipbench_oplog as theirs
    sound = json.load
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    for name in theirs.NAMES:
        assert CELL in lists[name], name
    names = [w["name"] for w in bench["workloads"]]
    later_cells = names[names.index("olmohybrid_train_T8k"):]
    metrics = [m["name"] for m in bench["per_layer"]]
    later_metrics = metrics[max(metrics.index(n) for n in theirs.NAMES) + 1:]
    assert CELL in later_cells and set(NEW) <= set(later_metrics)

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


# -- the four new readers on a window written out by hand ----------------------

PEAK, STEPS, BUSY = 197e12, 2, 0.5
FWD, AGAIN, BWD = ("jit(step)/visit_1/jvp()/checkpoint/",
                   "jit(step)/visit_1/transpose(jvp())/checkpoint/"
                   "rematted_computation/",
                   "jit(step)/visit_1/transpose(jvp())/checkpoint/")
# (op_name, seconds in each traced step)
OPS = [
    # a visit's stack
    (FWD + "causal_attention.9/jit(_fwd_pallas)/flash_fwd", 0.010),
    (FWD + "mul.12/dot_general:", 0.008),
    # its head and loss, in a region of their own: three passes
    (FWD + "mul.40/dot_general:", 0.006),
    (FWD + "softmax_with_cross_entropy.42/reduce:", 0.002),
    (AGAIN + "mul.40/dot_general:", 0.006),
    (AGAIN + "softmax_with_cross_entropy.42/reduce:", 0.002),
    (BWD + "softmax_with_cross_entropy.42/sub:", 0.003),
    (BWD + "mul.40/dot_general:", 0.012),
    # its gate, outside any region, and the glue after the loop
    ("jit(step)/visit_1/jvp(mul.44)/dot_general:", 0.0004),
    ("jit(step)/visit_1/transpose(jvp(mul.44))/dot_general:", 0.0006),
    ("jit(step)/jvp(exit_distribution.200)/cumsum:", 0.0002),
    ("jit(step)/transpose(jvp(elementwise_mul.202))/mul:", 0.0001),
    ("jit(step)/step_sum.210/add:", 0.0001),
    # the last visit's logits for a forward run: a row, no device time
    ("jit(step)/adam.300/mul:", 0.001)]
HEAD_S = 0.006 + 0.002 + 0.006 + 0.002 + 0.003 + 0.012
EXIT_S = 0.0004 + 0.0006 + 0.0002 + 0.0001 + 0.0001


def _rows(with_module=True):
    row = lambda seq, kind, module, region=None, weights=(): dict(
        {"seq": seq, "type": kind, "weights": weights, "region": region,
         "kept": None, "inputs": {}, "outputs": {}},
        **({"module": module} if with_module else {}))
    mul = lambda seq, module, weight, region=None: dict(
        row(seq, "mul", module, region, (weight,)), mkn=(8192, 2048, 2048),
        grads=("x", "w"), operand_dtype="bfloat16")
    return [row(9, "causal_attention", None, 9), mul(12, None, "ouro_l0_wo", 9),
            mul(40, "loop_head", "ouro_head", 17),
            row(42, "softmax_with_cross_entropy", "loop_head", 17),
            mul(44, "exit", "ouro_gate_w"),
            row(200, "exit_distribution", "exit"),
            row(202, "elementwise_mul", "exit"), row(210, "step_sum", "exit"),
            mul(220, "loop_head", "ouro_head")]


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
    run = _run(cfg, {"visit_loss": [10.8 * 40, 10.7 * 40, 10.6 * 40,
                                    10.26 * 40],
                     "exit_step": [1.9 * 40], "entropy": [1.2 * 40],
                     "steps": [40.0]})
    read = lambda name: cells.load_metric(name).read(run)
    assert read("loop_head_dev_share_pct") == pytest.approx(
        100.0 * STEPS * HEAD_S / BUSY)
    assert read("exit_dev_share_pct") == pytest.approx(
        100.0 * STEPS * EXIT_S / BUSY)
    assert read("exit_step_mean") == pytest.approx(1.9)
    assert read("loop_loss_last_over_first") == pytest.approx(0.95)
    out = capsys.readouterr().out
    assert "loop_head_dev_share_pct: 31.000 ms a step in the ops of module " \
        "loop_head: fwd 8.000, second 8.000, bwd 15.000" in out
    assert "exit_dev_share_pct: 1.400 ms a step in the ops of module exit: " \
        "fwd 0.700, second 0.000, bwd 0.700" in out
    # a scope under a visit's span is read as the op's own
    assert spans.parse_op_name(OPS[8][0])[1:] == ("mul.44", "fwd")
    assert spans.parse_op_name(OPS[4][0])[1] == "mul.40"
    assert oplog.pass_of(OPS[4][0]) == "second"


def test_the_new_readers_find_nothing_without_the_loop(monkeypatch):
    """On the parent of PR 59 no row is a loop's module's and the
    architecture counts nothing; a tree from before PR 55 states no
    ``module`` at all; an untraced run has no window. The readers
    return None and do not raise."""
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    read = lambda name, run: cells.load_metric(name).read(run)
    _plant_ledger(monkeypatch, _rows(with_module=False))
    for name in NEW:
        assert read(name, _run(cfg, {})) is None
    _plant_ledger(monkeypatch, [r for r in _rows() if r["module"] is None])
    for name in NEW[:2]:
        assert read(name, _run(cfg, {})) is None
    _plant_ledger(monkeypatch, _rows())
    assert read("exit_step_mean", _run(cfg, {"exit_step": [3.0]})) is None
    assert read("exit_step_mean", _run(cfg, {
        "exit_step": [3.0], "steps": [0.0]})) is None
    assert read("loop_loss_last_over_first", _run(cfg, {
        "visit_loss": [10.8]})) is None
    assert read("loop_loss_last_over_first", _run(cfg, {
        "visit_loss": [0.0, 10.8]})) is None
    for run in (_run(cfg, {}), _run(cfg, {})):
        run["spans"], run["trace"] = None, None        # an untraced run
        for name in NEW:
            assert read(name, run) is None
    monkeypatch.setattr(oplog, "ledger", lambda: None)
    for name in NEW[:2]:
        assert read(name, _run(cfg, {})) is None
