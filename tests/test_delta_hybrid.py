"""The dense hybrid of ``models/delta_hybrid.py`` (ISSUE 53) at small
sizes on the CPU: its program builds with each layer's mixer by its
kind and trains; the POST-norm wiring, the whole-projection QK-norm
with no position signal and the untied head against a few lines of
numpy; a linear layer against the equations of its ops, a row at a
time; a head's size is an argument and never a quotient; and the
rule's result is a candidate of the regions' plan, kept where it fits.
(The model against ``chipbench/reference/olmo_hybrid_lm.py`` and the
guide's share test for heads are
``tests/chipbench/test_chipbench_olmo_hybrid.py``'s.)
"""

import collections

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
import test_recompute as TR
from paddle_tpu.core import unique_name
from paddle_tpu.models import delta_hybrid as M
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import control_flow as CF
from paddle_tpu.ops import delta_rule as DR
from test_recompute_kinds import _named

SIZES = dict(vocab_size=64, seq_len=16, d_model=32, d_ffn=48, n_head=2,
             head_dim=8, n_linear_head=2, linear_key_head_dim=4,
             linear_value_head_dim=8, embedding_std=1.0, delta_chunk=8)
L, F = M.LINEAR, M.FULL


def _lm(prefix, layer_types=(L, L, F), recompute=True, adam=True, **kw):
    """(program, scope, feeds, fetch names: the loss and, with Adam,
    every parameter's gradient; the logits' variable)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        cost, logits = M.delta_hybrid_lm(
            layer_types=layer_types, recompute=recompute, name="m",
            **{**SIZES, **kw})
        fetch = (cost.name,)
        if adam:
            _, pg = fluid.optimizer.Adam(learning_rate=1e-2).minimize(cost)
            fetch += tuple(g.name for _, g in pg)
        fluid.Executor(fluid.CPUPlace()).run(startup)
    batch = T.make_lm_batch(np.random.RandomState(4), 2, 16, 64)
    feeds = {k: np.asarray(batch[k]) for k in ("src", "label", "mask")}
    return main, scope, feeds, fetch, logits


def _region_ops(main):
    return [[o.type for o in op.attr("sub_block").ops]
            for op in main.global_block().ops
            if op.type == "recompute_block"]


def test_the_program_builds_each_layer_by_its_kind_and_trains():
    main, scope, feeds, fetch, _ = _lm("b_")
    linear, _, full = (collections.Counter(ops) for ops in _region_ops(main))
    assert (linear["ssm_conv"], linear["l2_norm_scale"],
            linear["delta_gates"], linear["gated_delta_rule"],
            linear["gated_rms_norm"], linear["mul"]) == (3, 2, 1, 1, 1, 10)
    assert linear["rms_norm"] == 2 and "causal_attention" not in linear
    # the full layer: two norms over q and k whole, two on the
    # sublayers' outputs, and no rotation of any kind
    assert (full["causal_attention"], full["rms_norm"], full["mul"]) == (
        1, 4, 7)
    assert not {"rope", "qk_norm_rope", "gated_delta_rule"} & set(full)
    shapes = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    assert shapes["m_l0_wq"] == (32, 2 * 4) and shapes["m_l0_wv"] == (32, 16)
    assert shapes["m_l0_wa"] == shapes["m_l0_wb"] == (32, 2)
    assert shapes["m_l0_conv_q_w"] == (4, 8) and "m_l0_conv_q_b" not in shapes
    assert shapes["m_l0_gates_a_log"] == shapes["m_l0_gates_dt_bias"] == (2,)
    assert shapes["m_l0_o_norm"] == (8,) and shapes["m_l0_wo"] == (16, 32)
    # the QK-norm's weight is as long as the projection: 2 heads of 8
    assert shapes["m_l2_q_norm"] == shapes["m_l2_k_norm"] == (16,)
    assert shapes["m_head"] == (32, 64) and shapes["m_word_emb"] == (64, 32)
    exp_a = np.exp(np.asarray(scope.find_var("m_l0_gates_a_log")))
    np.testing.assert_allclose(exp_a, [4.0, 12.0], rtol=1e-6)
    losses = [float(TR._run(main, scope, feeds, fetch[:1])[0])
              for _ in range(8)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.2
    with pytest.raises(ValueError, match="a layer is"):
        _lm("bad_", layer_types=(L, "conv"))


def _rms(y, w, eps=1e-6):
    return y / np.sqrt((y * y).mean(-1, keepdims=True) + eps) * w


def _mlp(p, at, x):
    gate = x @ p[at + "_ffn_gate"]
    return (gate / (1 + np.exp(-gate)) * (x @ p[at + "_ffn_up"])) \
        @ p[at + "_ffn_down"]


def _numpy_full_layer(p, at, x, heads, d):
    """``x + RMSNorm(Attn(x))`` then ``x + RMSNorm(MLP(x))`` of one
    sequence x [T, d_model]: the norms on the sublayers' OUTPUTS, q and
    k normed over the whole projection, no position signal."""
    t = x.shape[0]
    q = _rms(x @ p[at + "_wq"], p[at + "_q_norm"]).reshape(t, heads, d)
    k = _rms(x @ p[at + "_wk"], p[at + "_k_norm"]).reshape(t, heads, d)
    v = (x @ p[at + "_wv"]).reshape(t, heads, d)
    s = np.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    attn = np.einsum("hqk,khd->qhd", w / w.sum(-1, keepdims=True), v)
    x = x + _rms(attn.reshape(t, heads * d) @ p[at + "_wo"], p[at + "_ln1"])
    return x + _rms(_mlp(p, at, x), p[at + "_ln2"])


def _numpy_linear_layer(p, at, x, heads, d_k, d_v):
    """The same block round the delta-rule mixer, the rule a row at a
    time."""
    t = x.shape[0]
    silu = lambda y: y / (1 + np.exp(-y))

    def conv(part, d):
        y, w = x @ p["%s_w%s" % (at, part)], p["%s_conv_%s_w" % (at, part)]
        y = np.concatenate([np.zeros((3, y.shape[1])), y])
        return silu(sum(w[i] * y[i:i + t] for i in range(4))).reshape(
            t, heads, d)

    unit = lambda y: y / np.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)
    q, k, v = unit(conv("q", d_k)) * d_k ** -0.5, unit(conv("k", d_k)), \
        conv("v", d_v)
    beta = 2 / (1 + np.exp(-(x @ p[at + "_wb"])))
    g = -np.exp(p[at + "_gates_a_log"]) * np.log1p(np.exp(
        x @ p[at + "_wa"] + p[at + "_gates_dt_bias"]))
    s, o = np.zeros((heads, d_k, d_v)), np.zeros((t, heads, d_v))
    for i in range(t):
        s = s * np.exp(g[i])[:, None, None]
        delta = beta[i][:, None] * (v[i] - np.einsum("hkv,hk->hv", s, k[i]))
        s = s + k[i][:, :, None] * delta[:, None, :]
        o[i] = np.einsum("hkv,hk->hv", s, q[i])
    y = _rms(o, p[at + "_o_norm"]) * silu(x @ p[at + "_wg"]).reshape(
        t, heads, d_v)
    x = x + _rms(y.reshape(t, heads * d_v) @ p[at + "_wo"], p[at + "_ln1"])
    return x + _rms(_mlp(p, at, x), p[at + "_ln2"])


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "regions"])
def test_the_blocks_wiring_is_a_few_lines_of_numpy(recompute):
    """A linear and a full layer, float32: the program's logits against
    numpy float64 that norms each sublayer's OUTPUT and none of its
    inputs, norms q and k over the whole projection, turns nothing, and
    reads a head of its own. A pre-norm block, a QK-norm a head, or a
    head tied to the embedding would part from it by far more than
    rounding."""
    main, scope, feeds, _, logits = _lm("w_", (L, F), recompute, adam=False)
    p = {v.name: np.asarray(scope.find_var(v.name), np.float64)
         for v in main.global_block().all_parameters()}
    for name in ("m_l0_ln1", "m_l1_q_norm", "m_l1_k_norm", "m_l0_o_norm",
                 "m_final_norm"):    # weights that are not all ones
        p[name] = p[name] * np.linspace(0.5, 1.5, p[name].shape[0])
        scope.set(name, p[name].astype(np.float32))
    got = TR._run(main.clone(for_test=True), scope, feeds, (logits.name,))[0]
    for b in range(2):
        x = p["m_word_emb"][feeds["src"][b]]
        x = _numpy_linear_layer(p, "m_l0", x, 2, 4, 8)
        x = _numpy_full_layer(p, "m_l1", x, 2, 8)
        want = _rms(x, p["m_final_norm"]) @ p["m_head"]
        np.testing.assert_allclose(got[b], want, atol=2e-4, rtol=2e-4)


def test_a_heads_size_is_an_argument_and_no_quotient():
    """Half the heads held of a hidden size of 32: ``d_model // n_head``
    would read 16 and 32; the projections are ``heads x the stated
    size`` wide."""
    main, *_ = _lm("q_", (L, F), n_head=1, n_linear_head=1, adam=False)
    shapes = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    assert shapes["m_l0_wq"] == (32, 4) and shapes["m_l0_wg"] == (32, 8)
    assert shapes["m_l1_wq"] == (32, 8) and shapes["m_l1_wo"] == (8, 32)


@pytest.mark.parametrize("path", ["walk", "kernels"])
def test_the_rules_result_is_a_candidate_of_the_plan_and_kept_where_it_fits(
        path, monkeypatch):
    """Two linear layers and a full one, every layer a region. With room
    the plan admits both rules' results (the gated norm after a rule
    reads it) beside every product, each carries its name once, and a
    traced gradient counts its bytes; with no room none is named, and
    the step is the same bits either way. Under the rule's kernels
    (interpret mode here: the path rule is steered in the test, the
    program has no option for it) the ONE kind holds the chunks'
    starting states too, under their own name beside the result's,
    all that the backward kernel reads of the forward."""
    kernels = path == "kernels"
    if kernels:
        monkeypatch.setattr(DR, "_resolve_path", lambda d_k, d_v, on_tpu,
                            force=None: force or "interpret")
    build = lambda: _lm("k_", delta_chunk=16 if kernels else 8)[:4]
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)
    names = (DR.DELTA_OUT, DR.DELTA_STATES)
    counted = lambda: [CF._KEPT_BYTES.value(name=n) for n in names]
    before = counted()
    kept = TR._step_jaxpr(*build())
    said = lambda what: int(CF._PLAN.value(kind=DR.DELTA_OUT, what=what))
    assert (said("candidates"), said("admitted")) == (2, 2)
    # a rule: the result [2, 16, 2 x 8] float32 and, under the kernels,
    # the ONE chunk's starting state of 2 x 2 heads, [4, 8] float32
    out, states = 2 * 16 * 16 * 4, 4 * 4 * 8 * 4 if kernels else 0
    assert said("admitted_bytes") == 2 * (out + states)
    assert [_named(kept, n) for n in names] == [2, 2 if kernels else 0]
    assert [now - was for now, was in zip(counted(), before)] \
        == [2 * out, 2 * states]
    # with both kept the forward kernel is dead in the second forward:
    # once a rule, where a region that keeps nothing runs it twice
    rules = lambda jaxpr: [TR._kernels(jaxpr).get(k, 0) for k in (
        "delta_rule_fwd", "delta_rule_bwd")]
    assert rules(kept) == ([2, 2] if kernels else [0, 0])
    with jax.disable_jit():
        with_room = TR._run(*build())
        monkeypatch.setattr(CF, "_device_limit", lambda ctx: 0)
        bare = TR._step_jaxpr(*build())
        assert [_named(bare, n) for n in names] == [0, 0]
        assert rules(bare) == ([4, 2] if kernels else [0, 0])
        without = TR._run(*build())
    assert all(np.abs(g).sum() > 0 for g in with_room[1:])
    for a, b in zip(with_room, without):
        np.testing.assert_array_equal(a, b)
