"""``models/granite_hybrid.py`` (ISSUE 64) on the CPU at a small size: a
layer is TWO sublayers in ONE region, the ops each kind builds, the
parameters' names, the four multipliers as ops of their own; each
multiplier moved changes the loss (none is read by nothing); a few
train steps under bf16 AMP and per-layer recompute against the same
steps with neither; the scan never runs row by row; and the regions'
plan of the benchmark's cell at its own size (the Program built,
nothing run)."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid                                      # noqa: E402
from paddle_tpu.models.granite_hybrid import granite_hybrid_lm  # noqa: E402
from paddle_tpu.monitor import metrics                          # noqa: E402
from paddle_tpu.ops import control_flow as CF                   # noqa: E402
from test_recompute_kinds import _cell_plan, _plan_says         # noqa: E402

KINDS, B, T, V = ["mamba", "attention", "mamba"], 2, 48, 64
PUBLISHED = dict(embedding_multiplier=12.0, residual_multiplier=0.22,
                 attention_multiplier=0.015625, logits_scaling=8.0)


def _built(recompute, seed=5, optimizer=True, **moved):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        cost, logits = granite_hybrid_lm(
            V, T, KINDS, d_model=32, d_ffn=48, n_head=4, n_kv_head=2,
            head_dim=8, n_ssm_head=16, ssm_head_dim=4, n_group=1, d_state=16,
            d_conv=4, a_max=17.0, embedding_std=1 / 12, recompute=recompute,
            scan_chunk=16, name="gh", **{**PUBLISHED, **moved})
        if optimizer:
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(cost)
    return main, startup, cost


def _feeds(seed=0):
    rng = np.random.RandomState(seed)
    return {"src": rng.randint(0, V, (B, T)).astype(np.int64),
            "label": rng.randint(0, V, (B, T)).astype(np.int64),
            "mask": np.ones((B, T), np.float32)}


def test_a_layer_is_two_sublayers_in_one_region():
    main, _, _ = _built(True)
    top = main.global_block().ops
    regions = [o for o in top if o.type == "recompute_block"]
    assert len(regions) == len(KINDS)
    kinds = [[m.type for m in r.attr("sub_block").ops] for r in regions]
    for ops in kinds:       # two norms, two multipliers, two residual adds
        assert ops.count("rms_norm") == 2 and ops[0] == "rms_norm"
        assert ops.count("scale") == 2 == ops.count("elementwise_add")
        assert ops[-2:] == ["scale", "elementwise_add"]
        assert ops.count("silu_mul") == 1
    mamba, attention, _ = kinds
    # five in-projections, the out-projection, the MLP's three
    assert mamba.count("mul") == 9 and mamba.count("ssm_conv") == 3
    for one in ("ssm_dt", "ssd_scan", "gated_group_norm"):
        assert mamba.count(one) == 1
    assert attention.count("mul") == 7 \
        and attention.count("causal_attention") == 1
    assert not {"rope", "qk_norm_rope"} & set(sum(kinds, []))
    # outside the regions: the embedding's multiplier, and the logits'
    # between the tied head and the loss
    outside = [o.type for o in top]
    assert outside.index("scale") == outside.index("lookup_table") + 1
    head = [i for i, o in enumerate(top) if o.type == "mul"][-1]
    assert top[head].attr("transpose_Y") and outside[head + 1] == "scale"
    assert [o.attr("scale") for o in top if o.type == "scale"] == [
        12.0, 0.125]
    inside = [o for r in regions for o in r.attr("sub_block").ops]
    assert {o.attr("scale") for o in inside if o.type == "scale"} == {0.22}
    (attn,) = [o for o in inside if o.type == "causal_attention"]
    assert attn.attr("scale") == 0.015625
    (scan, _) = [o for o in inside if o.type == "ssd_scan"]
    assert (scan.attr("n_head"), scan.attr("n_group")) == (16, 1)
    names = {p.name for p in main.global_block().all_parameters()}
    assert {"gh_word_emb", "gh_final_norm", "gh_l0_norm", "gh_l0_ffn_norm",
            "gh_l0_in_z", "gh_l0_in_x", "gh_l0_in_b", "gh_l0_in_c",
            "gh_l0_in_dt", "gh_l0_conv_x_w", "gh_l0_conv_x_b",
            "gh_l0_conv_b_w", "gh_l0_conv_c_b", "gh_l0_dt_bias",
            "gh_l0_scan_a_log", "gh_l0_scan_d", "gh_l0_gnorm", "gh_l0_out",
            "gh_l0_ffn_gate", "gh_l0_ffn_up", "gh_l0_ffn_down", "gh_l1_wq",
            "gh_l1_wk", "gh_l1_wv", "gh_l1_wo", "gh_l1_ffn_gate"} <= names
    assert not [n for n in names if "head" in n]        # the table is tied


def _first_loss(**moved):
    main, startup, cost = _built(False, optimizer=False, **moved)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        return float(exe.run(main, feed=_feeds(), fetch_list=[cost])[0])


@pytest.mark.parametrize("name,value", [
    ("embedding_multiplier", 3.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 8 ** -0.5 * 8), ("logits_scaling", 1.0)])
def test_a_multiplier_moved_changes_the_loss(name, value):
    """Same seed, same weights: the loss with one multiplier off its
    published value is another loss."""
    assert abs(_first_loss(**{name: value}) - _first_loss()) > 1e-4


def test_the_residual_multiplier_is_applied_in_float32_under_amp():
    """A sublayer's result is bfloat16 under AMP and 0.22 is 0.2197
    there: a `scale` built under ``amp.float32()`` widens what it reads
    and multiplies by 0.22; a plain one multiplies in bfloat16, 0.12%
    less on the mean."""
    from paddle_tpu import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        y = layers.fc(layers.data("x", [16]), 64, bias_attr=False)
        plain = layers.scale(y, 0.22)
        with fluid.amp.float32():
            wide = layers.scale(y, 0.22)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    feed = {"x": np.random.RandomState(0).randn(32, 16).astype("f")}
    with fluid.scope_guard(scope), fluid.amp.amp_guard(True):
        exe.run(startup)
        got_y, got_plain, got_wide = exe.run(
            main, feed=feed, fetch_list=[y, plain, wide], return_numpy=False)
    assert str(got_y.dtype) == str(got_plain.dtype) == "bfloat16"
    assert str(got_wide.dtype) == "float32"
    y32 = np.asarray(got_y, np.float32)
    np.testing.assert_array_equal(np.asarray(got_wide),
                                  y32 * np.float32(0.22))
    ratio = np.abs(np.asarray(got_plain, np.float32)).sum() \
        / np.abs(np.asarray(got_wide)).sum()
    assert 0.9980 < ratio < 0.9995


def _trained(recompute, amp, steps=4):
    main, startup, cost = _built(recompute)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope), fluid.amp.amp_guard(amp):
        exe.run(startup)
        return [float(exe.run(main, feed=_feeds(i % 2),
                              fetch_list=[cost])[0]) for i in range(steps)]


def test_it_trains_and_recompute_changes_nothing():
    """Four Adam steps on two batches in turn: float32 with and without
    the regions give the same losses; bf16 AMP follows them within its
    rounding; the loss falls. The scan took the chunk walk: never the
    step loop, never the kernels (there is no TPU here)."""
    counter = metrics.registry().get("ptpu_ssd_lowerings_total")
    counter.clear()
    plain, regions = _trained(False, False), _trained(True, False)
    np.testing.assert_allclose(regions, plain, rtol=2e-5)
    np.testing.assert_allclose(_trained(True, True), plain, rtol=2e-2)
    assert plain[2] < plain[0] and plain[3] < plain[1]
    assert np.log(V) - 0.5 < plain[0] < np.log(V) + 1.0
    assert {key[0] for key in counter.snapshot()} == {"chunked"}
    # one group of 16 heads, walked whole off the TPU, one Gram product
    assert {key[4:] for key in counter.snapshot()} == {("16", "16", "1")}


def test_the_plan_of_the_cell_at_its_own_size(monkeypatch):
    """`granite4hmicro_train_T8k` (10 regions of two sublayers, 8,192
    rows) under a v5e's limit, beside 9.27 GB of parameters and
    moments: 78 candidate products (a Mamba-2 layer's five
    in-projections and its MLP's gate, up and hidden; attention's q, k,
    v: a sublayer's LAST product goes through its multiplier into the
    stream and is no candidate, `scale` reading none of its operand),
    74 of them kept. A Mamba-2 region counts the chunk states its scan
    saves beside the scan's result."""
    _cell_plan(monkeypatch, "granite4hmicro_train_T8k")
    candidates, admitted = _plan_says(CF.MUL_OUT)[:2]
    assert candidates == 9 * 7 + 5 + 10 * 1 == 78 and admitted == 74
    last = CF._LAST
    assert last["state"] == pytest.approx(772160448 * 12, rel=1e-3)
    states = 4 * 8192 * 4096 * 128 // 128
    assert last["region"] > 2 * (states + 8192 * 4096 * 4)
