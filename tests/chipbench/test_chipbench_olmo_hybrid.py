"""The cell ``olmohybrid_train_T8k`` (ISSUE 53): its files hold to their
source, the cell rehearses on the CPU through ``run.py``, the program's
logits and first loss lie under the architecture's two limits against
``reference/olmo_hybrid_lm.py`` and the fp8 control's do not, both
controls part from the reference, each planted fault fails ``correct``
through the driver, the arithmetic is the issue's, the two new readers
read a small window written out by hand, and THE GUIDE'S SHARE TEST FOR
HEADS: the two shares' mixer outputs add up to the uncut reference's,
the MLP and the block norms counted once."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import arith, cells, control, peaks, tracing  # noqa: E402
from chipbench.drivers import train_steps                   # noqa: E402
from test_chipbench_lfm2 import _op                         # noqa: E402

CELL = "olmohybrid_train_T8k"
CONFIG = "olmo-hybrid-7b-train-tp2"
DEV = "/device:TPU:0"
REDUCED = ["num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "linear_num_key_heads", "linear_num_value_heads", "vocab_size"]
NEW = ("delta_rule_dev_share_pct", "delta_glue_dev_share_pct")
LINEAR, FULL = "linear_attention", "full_attention"


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
        if row["name"] == "Olmo-Hybrid-7B":
            assert cfg["published"] == row["config"]
            assert cfg["source"] == row["source_url"]
    assert cells.published_faults(cfg) == []
    assert cfg["reduced"] == REDUCED
    assert all(not cells.is_width(key) for key in REDUCED)
    assert [cfg[key] for key in REDUCED] == [4, 15, 15, 15, 15, 12544]
    # every width as published, the list of kinds whole
    for key, value in cfg["published"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"], cfg["linear_allow_neg_eigval"],
            cfg["rms_norm_eps"], cfg["tie_word_embeddings"],
            cfg["rope_parameters"]) == (
                3840, 11008, 96, 192, 4, True, 1e-6, False,
                {"rope_theta": None})
    # a head's size is stated, as published (3840 / 30), and no key of
    # the source: with 15 heads held a quotient would read 256
    assert cfg["head_dim"] == 128 == cfg["published"]["hidden_size"] \
        // cfg["published"]["num_attention_heads"]
    assert "head_dim" not in cfg["published"]
    assert len(cfg["layer_types"]) == 32
    assert cfg["layer_types"][:4] == [LINEAR, LINEAR, LINEAR, FULL]
    assert cfg["layer_types"] == [LINEAR, LINEAR, LINEAR, FULL] * 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    for key in REDUCED[1:5]:
        assert cfg[key] * 2 == cfg["published"][key]
    assert "2 chips share each layer's MIXER by heads" in cfg["deployment"]
    assert "28 layers" in cfg["deployment"]
    for said in ("deployment", "parameters", "train_dtype"):
        assert cfg[said]
    assert "766.2 M" in cfg["parameters"] and "12.26 GB" in cfg["parameters"]
    assert set(cfg["assumed"]) >= {
        "head_dim", "block", "position_signal", "linear_attention",
        "initialisation", "seq_len", "delta_chunk"}
    tiny = {**cfg, **cfg["rehearse"]}
    assert tiny["layer_types"][:4] == cfg["layer_types"][:4]
    mix = cell["traffic_file"]
    assert (mix["driver"], mix["batch"], mix["seq_len"], mix["n_batches"],
            mix["warmup_steps"], mix["trace_steps"], mix["check_rows"]) == (
                "train_steps", 1, 8192, 4, 3, 6, 64)
    assert cell["chips"] == 1 and cell["traffic"] == "pretrain_T8k_b1"
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {
        "flash_roof_pct", "train_mfu_pct", "matmul_roof_pct",
        "dense_matmul_roof_pct", "dense_matmul_fwd_roof_pct",
        "dense_matmul_bwd_roof_pct", "second_forward_dev_share_pct",
        "norm_rope_dev_share_pct", "device_idle_pct.train",
        "setup_enter_s", "xent_dev_share_pct",
        "optimizer_dev_share_pct", "unscoped_dev_share_pct",
        "step_interval_ms.train", "step_stall_pct.train",
        "exe_step_ms.train", "exe_self_ms.train", "step_host_ms.train",
        "setup_trace_lower_s.train", "setup_compile_s.train"} <= names
    # a dense model with no scan and no short convolution of LFM2's kind
    assert not {"expert_matmul_roof_pct", "moe_glue_dev_share_pct",
                "scan_hbm_roof_pct", "ssm_glue_dev_share_pct",
                "short_conv_dev_share_pct"} & names
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for name in NEW:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        reader = cells.load_metric(name)
        assert entry == {"name": name, "unit": reader.UNIT,
                         "better": "lower", "source": reader.SOURCE,
                         "layer": reader.LAYER, "moves": reader.MOVES,
                         "workloads": [CELL]}
        assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            "%", "device_trace", "kernels", "tokens_per_s")
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == REDUCED and config["source"] == cfg["source"]
    assert cell["config"] == CONFIG


def test_arithmetic_is_the_issues():
    cfg = cells.load_cell(ROOT, CELL)["config_file"]
    arch = cells.load_arch("olmo_hybrid")
    d, heads = 3840, 15
    linear = d * heads * (2 * 96 + 3 * 192 + 2)
    full, mlp = 4 * d * heads * 128, 3 * d * 11008
    assert [round(x / 1e4) for x in (linear, full, mlp)] == [4435, 2949,
                                                             12681]
    assert arch.mixer_parameters(cfg, LINEAR) == linear
    assert arch.mixer_parameters(cfg, FULL) == full
    touched = 3 * (linear + mlp) + full + mlp + d * 12544
    assert arch.touched_parameters(cfg) == touched
    assert round(touched / 1e5) == 7180                     # 718.0 M
    assert arith.train_flops_per_token(cfg, 0) == 6 * touched
    # what this chip holds: 766.2 M parameters, 12.26 GB at 16 bytes
    held = (3 * (linear + 4 * heads * (2 * 96 + 192) + 2 * heads + 192
                 + mlp + 2 * d)
            + full + 2 * heads * 128 + mlp + 2 * d + 2 * 12544 * d + d)
    assert round(held / 1e5) == 7662 and round(held * 16 / 1e7) == 1226
    t = 8192
    causal = t * (t + 1) // 2
    assert arith.flash_flops_per_step(cfg, 1, t) == 14 * 128 * heads * causal
    assert arith.train_flops_per_token(cfg, t) == pytest.approx(
        6 * touched + arith.flash_flops_per_step(cfg, 1, t) / t)
    # the issue's reckoning: 36.5 TFLOP a step with the rules, 35.3 of
    # dense products, 0.9 of the one attention layer, 0.2 of the rules
    dense = 6 * touched * t
    flash = arith.flash_flops_per_step(cfg, 1, t)
    rules = arch.delta_rule_flops_per_step(cfg, 1, t)
    assert [round(x / 1e11) for x in (dense, flash, rules)] == [353, 9, 2]
    assert round((dense + flash + rules) / 1e11) == 364
    a_chunk = (6 * 64 * 64 * 96 + 4 * 64 * 64 * 192 + 6 * 64 * 96 * 192
               + 2 * 64 ** 3 // 3)
    assert rules == 3 * 3 * heads * 128 * a_chunk and rules < 0.01 * dense
    # 0.22 TFLOP and 2.56 GB: 1.1 ms and 3.1 ms at the chip's peaks
    assert round(rules / 197e12 * 1e4) == 11
    moved = arch.delta_rule_bytes_per_step(cfg, 1, t)
    rows = t * heads
    assert moved == 3 * (2 * rows * (2 * 576 + 8) + rows * (4 * 576 + 16)
                         + 2 * 128 * heads * 96 * 192 * 4)
    assert round(moved / 819e9 * 1e4) == 31
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
    assert "the reference handed the program's choices" not in p.stdout


def test_the_program_lies_under_its_limits_and_the_control_does_not():
    """``control.py``'s readings at the rehearsal's size: the program's
    bf16-AMP logits against the float32 reference under
    ``TRAIN_LOGITS_RTOL``, the fp8 control over it, by three times the
    program's."""
    arch = cells.load_arch("olmo_hybrid")
    program, fp8, routed = control.readings(_tiny_cell(), 13, False)
    assert not routed
    assert program <= arch.TRAIN_LOGITS_RTOL < fp8
    assert fp8 >= 3 * program


@pytest.mark.parametrize("which", ["control_logits_at",
                                   "bf16_state_logits_at"])
def test_a_control_parts_from_the_reference(which):
    """Both controls of ``TRAIN_LOGITS_RTOL`` (fp8 operands in every
    matmul; the rule's state and decays held in bfloat16 between rows)
    run on the program's own parameters at the rehearsal's size and
    give logits of the reference's shape that are NOT the reference's."""
    from chipbench import traffic
    from chipbench.reference import compare
    cell = _tiny_cell()
    cfg, mix = cell["config_file"], cell["traffic_file"]
    rows = mix["check_rows"]
    with train_steps.trainer(cell, 13, False) as t:
        params = t.arch.params_of_program(t.main, t.scope, cfg)
    one = traffic.lm_batches(13, 1, 1, mix["seq_len"], cfg["vocab_size"])[0]
    want, got = (train_steps.reference_rows(fn, cfg, params, one, rows)
                 for fn in (t.arch.logits_at, getattr(t.arch, which)))
    assert got.shape == want.shape == (rows, cfg["vocab_size"])
    assert np.isfinite(got).all()
    assert compare.logits_error(got, want) > 1e-5


def test_what_the_embeddings_scale_buys_and_what_it_costs():
    """The witness of ``embedding_init_std`` 4.0, with no program in it:
    the float32 REFERENCE with bfloat16 operands in its matmuls and
    nothing else changed (the rule float32), against itself in float32,
    at a middle size with the published head widths (2 heads, keys of
    96, values of 192, hidden 128, 1,024 rows: the last 64), on four
    seeds. With the embedding drawn from N(0, 1) bf16 operands ALONE
    read over ``TRAIN_LOGITS_RTOL`` and spread widely (1.7e-2 to
    5.4e-2): the tail is the rule's own sensitivity where the stream is
    as small as a sublayer's output, no fault of the op, and no limit
    holds it with room. At 4.0 they read a quarter of the limit and lie
    close together (3.7e-3 to 6.0e-3). THE COST, which the cell's
    ``why`` names: the second control, the rule's state held in
    bfloat16, reads at or over the limit at 1.0 (2.5e-2 to 7.9e-2,
    inside the bf16 operands' own noise there: it separates at
    neither) and a 25th of it at 4.0 (about 1e-3): the comparison
    cannot see the state's precision. The rehearsal needs 8.0 for the
    same reason at its size: keys of 8 leave the rule an eighth of the
    dimensions to average rounding over (the program reads 9.8e-3 to
    1.5e-1 at 4.0 there, 4.5e-3 to 1.3e-2 at 8.0)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from chipbench.reference import compare, olmo_hybrid_lm
    arch = cells.load_arch("olmo_hybrid")
    limit, t, rows = arch.TRAIN_LOGITS_RTOL, 1024, 64
    cfg = {**_tiny_cell()["config_file"], "hidden_size": 128,
           "intermediate_size": 256, "head_dim": 64,
           "num_attention_heads": 2, "num_key_value_heads": 2,
           "linear_num_key_heads": 2, "linear_num_value_heads": 2,
           "linear_key_head_dim": 96, "linear_value_head_dim": 192,
           "delta_chunk": 64, "embedding_init_std": 1.0}
    logits = jax.jit(lambda params, tokens, **how: olmo_hybrid_lm.logits_at(
        params, tokens, t - rows, rows, cfg, **how),
        static_argnames=("operands", "state_dtype"))
    operands, state = {1.0: [], 4.0: []}, {1.0: [], 4.0: []}
    for seed in (20, 21, 22, 23):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), fluid.scope_guard(scope):
            arch.build(cfg, t)
            fluid.Executor(fluid.CPUPlace()).run(startup)
            params = jax.tree.map(np.asarray, arch.params_of_program(
                main, scope, cfg))
        tokens = jnp.asarray(np.random.RandomState(seed).randint(
            0, cfg["vocab_size"], t))
        for std in operands:
            scaled = {**params, "word_emb": params["word_emb"] * std}
            want = np.asarray(logits(scaled, tokens))
            for into, how in ((operands, {"operands": jnp.bfloat16}),
                              (state, {"state_dtype": jnp.bfloat16})):
                into[std].append(compare.logits_error(
                    np.asarray(logits(scaled, tokens, **how)), want))
    assert max(operands[1.0]) > limit
    assert max(operands[1.0]) > 2.5 * min(operands[1.0])
    assert max(operands[4.0]) < limit / 2
    assert max(operands[4.0]) < 2.5 * min(operands[4.0])
    assert min(state[1.0]) > limit / 2
    assert max(state[4.0]) < limit / 10


@pytest.mark.parametrize("fault", [
    "sound", "beta_not_doubled", "the_taps_reversed", "the_query_unscaled",
    "the_gate_before_the_norm", "no_decay", "scores_unscaled"])
def test_a_planted_fault_fails_correct(monkeypatch, fault):
    """The whole driver at the rehearsal's size. A program whose rule
    steps by ``sigmoid`` and not twice it, whose convolutions run back
    to front, whose query keeps its unit norm, which gates before it
    norms, which never forgets or whose softmax is not scaled parts
    from the reference by more than a limit, and ``correct`` comes out
    false."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.models import delta_hybrid as model
    from paddle_tpu.ops import delta_rule as DR
    from paddle_tpu.ops import selective_scan as SS
    with_kw = lambda fn, **fixed: lambda *a, **kw: fn(*a, **{**kw, **fixed})
    if fault == "beta_not_doubled":
        monkeypatch.setattr(model, "delta_hybrid_lm",
                            with_kw(model.delta_hybrid_lm, beta_scale=1.0))
    if fault == "the_taps_reversed":
        sound_taps = SS.causal_taps
        monkeypatch.setattr(SS, "causal_taps", lambda x, w, *a: sound_taps(
            x, w[::-1], *a))
    if fault == "the_query_unscaled":
        sound_norm = DR.l2_norm_scale
        monkeypatch.setattr(DR, "l2_norm_scale",
                            lambda x, n, scale=1.0, eps=1e-6: sound_norm(
                                x, n, 1.0, eps))
    if fault == "the_gate_before_the_norm":
        sound_gated = DR.gated_rms_norm
        monkeypatch.setattr(
            DR, "gated_rms_norm", lambda x, gate, scale, eps=1e-6:
            sound_gated(x.astype(jnp.float32) * jax.nn.silu(
                gate.astype(jnp.float32)), jnp.full_like(gate, 1.278),
                scale, eps))     # (silu(1.278) = 1: no gate behind)
    if fault == "no_decay":
        sound_gates = DR.delta_gates
        monkeypatch.setattr(DR, "delta_gates", lambda *a: (
            0.0 * sound_gates(*a)[0], sound_gates(*a)[1]))
    if fault == "scores_unscaled":
        monkeypatch.setattr(fluid.layers, "causal_attention", with_kw(
            fluid.layers.causal_attention, scale=1.0))
    said = []
    line = train_steps.run(_tiny_cell(), 7, 0.05, jax.devices("cpu"),
                           time.perf_counter(), None, said.append)
    assert line["failed"] == 0
    assert line["correct"] is (fault == "sound"), said
    counters = line["train"]["counters"]
    tiny = _tiny_cell()["config_file"]
    tag = "chunked/%d/%d/%d/%d" % (
        tiny["delta_chunk"], tiny["linear_num_value_heads"],
        tiny["linear_key_head_dim"], tiny["linear_value_head_dim"])
    assert counters["delta_rule_lowerings"].get(tag, 0) >= 3
    assert not [k for k in counters["delta_rule_lowerings"]
                if k.startswith("steps")]


# -- the guide's share test, for heads ----------------------------------------

def _whole_layer_params(seed, kind, d, heads, d_k, d_v, d_head):
    r = np.random.RandomState(seed)
    w = lambda *shape: (r.randn(*shape) * shape[0] ** -0.5).astype(
        np.float32)
    if kind == LINEAR:
        return {"wq": w(d, heads * d_k), "wk": w(d, heads * d_k),
                "wv": w(d, heads * d_v), "wg": w(d, heads * d_v),
                "wa": w(d, heads), "wb": w(d, heads),
                "conv_q": r.uniform(-.5, .5, (4, heads * d_k)).astype("f4"),
                "conv_k": r.uniform(-.5, .5, (4, heads * d_k)).astype("f4"),
                "conv_v": r.uniform(-.5, .5, (4, heads * d_v)).astype("f4"),
                "a_log": np.log(r.uniform(0.5, 16, heads)).astype("f4"),
                "dt_bias": r.uniform(-5, -2, heads).astype("f4"),
                "o_norm": r.uniform(0.5, 1.5, d_v).astype("f4"),
                "wo": w(heads * d_v, d)}
    return {"wq": w(d, heads * d_head), "wk": w(d, heads * d_head),
            "wv": w(d, heads * d_head),
            "q_norm": r.uniform(0.5, 1.5, heads * d_head).astype("f4"),
            "k_norm": r.uniform(0.5, 1.5, heads * d_head).astype("f4"),
            "wo": w(heads * d_head, d)}


def _share(p, first, held, widths):
    """Heads ``first .. first + held - 1`` of a layer's mixer: the
    columns of the projections (and of the filters and the whole-
    projection norms) and the rows of ``wo`` that are theirs."""
    out = {}
    for key, value in p.items():
        width = widths.get(key)
        if width is None:                      # one weight for every head
            out[key] = value
        elif key == "wo":
            out[key] = value[first * width:(first + held) * width]
        else:
            out[key] = value[..., first * width:(first + held) * width]
    return out


def test_two_shares_of_a_layers_heads_add_up_to_the_uncut_layer():
    """The deployment's claim, on the REFERENCE at a small size: 8
    heads, two chips of 4. A linear layer's heads are independent (its
    convolutions are depthwise, the l2 norms and the gated norm a
    head's own, the gates a head's own) and ``W_o`` sums over heads,
    so the two shares' mixer outputs add up to the uncut layer's,
    exactly up to float32 rounding. The full layer's do where each
    share is handed the WHOLE projection's mean square for its q and k
    norms (the one number a row the pair would exchange; a test
    argument of the reference, nothing in the program); over its own
    columns alone a share's norm differs, and the sum does NOT add up:
    the configuration says so. The MLP and the block norms are whole
    on each chip and counted once: the block's output from the summed
    mixers is the uncut block's."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import olmo_hybrid_lm as R
    d, heads, d_k, d_v, d_head, t, eps = 32, 8, 4, 8, 8, 24, 1e-6
    mm = lambda a, b: a @ b
    x = jnp.asarray(np.random.RandomState(0).randn(t, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        # a linear layer
        p = _whole_layer_params(1, LINEAR, d, heads, d_k, d_v, d_head)
        widths = {"wq": d_k, "wk": d_k, "wv": d_v, "wg": d_v, "wa": 1,
                  "wb": 1, "conv_q": d_k, "conv_k": d_k, "conv_v": d_v,
                  "a_log": 1, "dt_bias": 1, "wo": d_v}
        whole = R.linear_mixer(p, x, heads, d_k, d_v, eps, mm)
        parts = [R.linear_mixer(_share(p, first, 4, widths), x, 4, d_k, d_v,
                                eps, mm) for first in (0, 4)]
        np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                                   np.asarray(whole), atol=2e-6, rtol=2e-6)
        assert float(jnp.max(jnp.abs(parts[1]))) > 1e-2
        # the full layer
        p = _whole_layer_params(2, FULL, d, heads, d_k, d_v, d_head)
        widths = dict.fromkeys(("wq", "wk", "wv", "q_norm", "k_norm", "wo"),
                               d_head)
        whole = R.full_mixer(p, x, heads, d_head, eps, mm)
        squares = tuple(jnp.mean(jnp.square(x @ p[key]), -1, keepdims=True)
                        for key in ("wq", "wk"))
        parts = [R.full_mixer(_share(p, first, 4, widths), x, 4, d_head, eps,
                              mm, mean_squares=squares) for first in (0, 4)]
        np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                                   np.asarray(whole), atol=2e-6, rtol=2e-6)
        alone = [R.full_mixer(_share(p, first, 4, widths), x, 4, d_head, eps,
                              mm) for first in (0, 4)]
        assert float(jnp.max(jnp.abs(alone[0] + alone[1] - whole))) > 1e-3
        # the MLP and the block's two norms, once
        rest = {"ln1": jnp.linspace(0.5, 1.5, d), "ln2": jnp.ones(d),
                "ffn": tuple(jnp.asarray(np.random.RandomState(3 + i).randn(
                    *shape) * 0.2, jnp.float32) for i, shape in enumerate(
                        ((d, 48), (d, 48), (48, d))))}

        def block(mixed):
            y = x + R._rms(mixed, rest["ln1"], eps)
            return y + R._rms(R.mlp(rest, y, mm), rest["ln2"], eps)
        np.testing.assert_allclose(np.asarray(block(parts[0] + parts[1])),
                                   np.asarray(block(whole)), atol=1e-5)


# -- the new readers on a window written out by hand ---------------------------

def _run_of(ops, step_s, cfg, seq_len=8192):
    modules = [{"program": "step", "start": i * step_s, "dur": step_s}
               for i in range(2)]
    rows = [{"plane": DEV, "line": tracing.OP_LINE, "name": o["name"],
             "start": o["start"], "dur": o["dur"]} for o in ops]
    rows += [{"plane": DEV, "line": tracing.MODULE_LINE,
              "name": "jit_step(1)", "start": m["start"], "dur": m["dur"]}
             for m in modules]
    return {"trace": tracing.reduce_rows(rows, 1),
            "spans": {"host": [], "ops": ops, "modules": modules,
                      "compiles": None},
            "config": cfg, "chips": 1,
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "train": {"batch": 1, "seq_len": seq_len,
                      "tokens_per_step": seq_len, "counters": {}}}


@pytest.fixture()
def window():
    """Two traced steps of a program ``step`` whose layers are
    recompute regions: per step a linear layer's convolution, l2 norm,
    gates, rule (its products and its walk's loop, forward, recomputed
    and backward) and gated norm beside a projection, and the full
    layer's flash kernels, named in the form the compiler gives a
    region's ops in a trace."""
    fwd = "jit(step)/jvp(%s)/"
    again = "jit(step)/transpose(jvp(jvp()))/checkpoint/" \
        "rematted_computation/%s/"
    bwd = "jit(step)/transpose(jvp(jvp()))/checkpoint/%s/"
    rule = "gated_delta_rule.20"
    ops, at = [], 0.0
    for _ in range(2):
        for (kind, name), dur, kernel in (
                (("fusion", fwd % "mul.11" + "dot_general:"), 4e-3, False),
                (("fusion", fwd % "ssm_conv.12" + "mul:"), 3e-4, False),
                (("fusion", fwd % "l2_norm_scale.15" + "mul:"), 2e-4, False),
                (("fusion", fwd % "delta_gates.18" + "exp:"), 1e-4, False),
                (("fusion", fwd % rule + "dot_general:"), 2e-3, False),
                (("while", fwd % rule + "while:"), 5e-3, False),
                (("fusion", fwd % "gated_rms_norm.22" + "mul:"), 4e-4, False),
                (("flash_fwd", fwd % "causal_attention.40" + "full/"
                  "jit(_fwd_pallas)/flash_fwd/pallas_call:"), 4e-3, True),
                (("fusion", again % "ssm_conv.12" + "mul:"), 3e-4, False),
                (("while", again % rule + "while:"), 5e-3, False),
                (("flash_bwd", bwd % "causal_attention.40" + "full/"
                  "jit(_bwd_pallas)/flash_bwd/pallas_call:"), 8e-3, True),
                (("while", bwd % rule + "while:"), 9e-3, False),
                (("fusion", bwd % rule + "transpose:"), 3e-3, False),
                (("fusion", bwd % "gated_rms_norm.22" + "mul:"), 6e-4, False),
                (("fusion", bwd % "ssm_conv.12" + "pad:"), 5e-4, False)):
            ops.append(_op(kind, dur, name, at, kernel))
            at += dur
    return _run_of(ops, at / 2, cells.load_cell(ROOT, CELL)["config_file"])


def test_the_new_readers_on_a_window_by_hand(window, capsys):
    busy = window["trace"]["busy_s"]
    read = lambda name: cells.load_metric(name).read(window)
    rule = 2 * (2e-3 + 5e-3 + 5e-3 + 9e-3 + 3e-3)
    assert read(NEW[0]) == pytest.approx(100 * rule / busy, rel=1e-9)
    said = capsys.readouterr().out
    assert "while 0.038000, fusion 0.010000" in said
    glue = 2 * (3e-4 + 2e-4 + 1e-4 + 4e-4 + 3e-4 + 6e-4 + 5e-4)
    assert read(NEW[1]) == pytest.approx(100 * glue / busy, rel=1e-9)
    said = capsys.readouterr().out
    assert ("ssm_conv 0.002200 s, l2_norm_scale 0.000400 s, delta_gates "
            "0.000200 s, gated_rms_norm 0.002000 s") in said
    # their neighbours read their own ops and none of these
    peak = window["peaks"]["flops_bf16"]
    flash = arith.flash_flops_per_step(window["config"], 1, 8192)
    assert read("flash_roof_pct") == pytest.approx(
        100 * 2 * flash / peak / (2 * 12e-3), rel=1e-9)
    assert read("short_conv_dev_share_pct") is None
    assert read("scan_hbm_roof_pct") is None


def test_the_new_readers_find_nothing_in_a_program_without_the_rule():
    """In Phi-4-mini-flash's cell, whose convolution is the op
    ``ssm_conv`` too and which runs no delta rule, on this PR's parent
    as on its change: both readers return None and do not raise, the
    glue's also where ``ssm_conv`` has device time."""
    cfg = cells.load_cell(ROOT, "phi4flash_train_T8k")["config_file"]
    ops = [_op("fusion", 1e-3, "jit(step)/jvp(mul.3)/dot_general:", 0.0),
           _op("fusion", 1e-3, "jit(step)/jvp(ssm_conv.4)/mul:", 1e-3)]
    run = _run_of(ops, 1e-3, cfg)
    for name in NEW:
        assert cells.load_metric(name).read(run) is None
    run.pop("trace")                   # an untraced run
    run["spans"] = None
    for name in NEW:
        assert cells.load_metric(name).read(run) is None


# -- accepted tests that pin the benchmark's lists ---------------------------

@pytest.mark.parametrize("module, test", [
    ("test_chipbench_oplog", "test_the_entries_in_benchmark_json"),
    ("test_chipbench_norm_rope",
     "test_the_entry_names_the_block_diffusion_cell_alone")])
def test_a_pinned_entry_is_as_its_pr_left_it(monkeypatch, module, test):
    """Two accepted tests pin lists of ``BENCHMARK.json`` that this PR
    had to append to: ``test_chipbench_oplog.py`` asserts that PR 51's
    four metrics are the LAST entries of ``per_layer`` and that their
    lists are every cell's; ``test_chipbench_norm_rope.py`` that
    ``norm_rope_dev_share_pct`` lists the block-diffusion cell alone,
    and this cell's eleven ``rms_norm`` ops have no other reader. The
    files are the benchmark's, and no PR but a ``benchmark`` PR may edit
    them (``tests/conftest.py`` says where they are marked). Everything
    they assert still has to hold of what their PRs wrote: here each
    runs against the benchmark with what this PR appended taken off
    again. What a stripped copy cannot see is asserted first: this cell
    IS on those lists. (A ``benchmark`` PR should make the pinned tests
    read the cells off ``BENCHMARK.json`` and drop this copy with the
    markers.)"""
    import importlib
    theirs = importlib.import_module(module)
    sound = json.load
    lists = {m["name"]: m.get("workloads", ())
             for m in cells.load_json(
                 os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]}
    for name in ("dense_matmul_roof_pct", "dense_matmul_fwd_roof_pct",
                 "dense_matmul_bwd_roof_pct", "second_forward_dev_share_pct",
                 "norm_rope_dev_share_pct"):
        assert CELL in lists[name], name

    def as_pr_52_left_it(f):
        bench = sound(f)
        if not (isinstance(bench, dict) and "per_layer" in bench):
            return bench
        without = lambda m: {**m, "workloads": [
            w for w in m["workloads"] if w != CELL]} if "workloads" in m \
            else m
        return {**bench,
                "workloads": [w for w in bench["workloads"]
                              if w["name"] != CELL],
                "end_to_end": [without(m) for m in bench["end_to_end"]],
                "per_layer": [without(m) for m in bench["per_layer"]
                              if m["name"] not in NEW]}
    monkeypatch.setattr(theirs.json, "load", as_pr_52_left_it)
    getattr(theirs, test)()
