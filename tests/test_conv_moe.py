"""The hybrid of short convolutions and attention with routed experts
(ISSUE 49, ``models/conv_moe.py``) at small sizes with seeded weights
on the CPU: the router's epsilon (`moe.route` with and without it, the
old call what it was), the shares of a 4-way expert-parallel group
adding up to the uncut layer, and the whole small model against the
benchmark's float32 reference (``chipbench/reference/lfm2_lm.py``):
loss, logits, every parameter's gradient, and a conv layer's output at
a row standing still when later rows move.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import small_model_test
from paddle_tpu.parallel import moe

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference import compare, lfm2_lm  # noqa: E402


def _r(*shape, seed=0, scale=0.5):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


# -- the router's epsilon -------------------------------------------------------

@pytest.mark.parametrize("eps", [0.0, 1e-6, 0.25],
                         ids=["none", "published", "large"])
def test_route_adds_the_epsilon_to_the_chosen_weights_sum(eps):
    """Sigmoid scores, the top-4 of score + bias, the unbiased scores
    at the chosen over (their sum + eps): against the same written out;
    with no epsilon the call that leaves it out, bit for bit."""
    x, wr, bias = _r(64, 16, seed=1, scale=1.0), _r(16, 32, seed=2), \
        _r(32, seed=3, scale=0.1)
    kw = dict(score="sigmoid", bias=bias, scaling=1.0)
    probs, w, idx = moe.route(x, wr, 4, True, norm_eps=eps, **kw)
    score = jax.nn.sigmoid(jnp.dot(x, wr, precision="highest"))
    _, top_i = jax.lax.top_k(score + bias, 4)
    top_p = jnp.take_along_axis(score, top_i, 1)
    np.testing.assert_array_equal(idx, top_i)
    np.testing.assert_allclose(
        w, top_p / (jnp.sum(top_p, -1, keepdims=True) + eps), rtol=1e-6)
    if not eps:
        assert float(jnp.max(jnp.abs(jnp.sum(w, -1) - 1.0))) < 1e-6
        for got, was in zip((probs, w, idx), moe.route(x, wr, 4, True, **kw)):
            np.testing.assert_array_equal(got, was)
    else:
        plain = moe.route(x, wr, 4, True, **kw)[1]
        shrunk = np.asarray(plain) * np.asarray(
            jnp.sum(top_p, -1, keepdims=True)
            / (jnp.sum(top_p, -1, keepdims=True) + eps))
        np.testing.assert_allclose(w, shrunk, rtol=1e-6)


def _layer(n=48, d=16, f=12, e=32, seed=80):
    return (_r(n, d, seed=seed, scale=1.0), _r(d, e, seed=seed + 2),
            _r(e, seed=seed + 6, scale=0.05),
            _r(e, d, f, seed=seed + 3, scale=d ** -0.5),
            _r(e, d, f, seed=seed + 4, scale=d ** -0.5),
            _r(e, f, d, seed=seed + 5, scale=f ** -0.5))


_CFG4 = {"num_experts_per_tok": 4, "published": {"num_experts": 32},
         "norm_topk_prob": True, "routed_scaling_factor": 1}


def _reference(x, wr, bias, wg, wu, wd, first, held):
    p = {"router": wr, "bias": bias, "w_gate": wg[first:first + held],
         "w_up": wu[first:first + held], "w_down": wd[first:first + held]}
    return lfm2_lm.expert_layer(p, x, _CFG4, first, held,
                                lambda a, b: a @ b)


def test_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test. 4 chips holding 8 of 32 experts each
    (`first_expert` 0, 8, 16, 24), sigmoid top-4 under a selection bias,
    1e-6 in the weights' sum: the program's four shares add up to the
    reference's layer that holds all 32, and each share is the
    reference's own share. Nothing is counted twice: there is no shared
    expert, and the router, which every chip computes alike, adds
    nothing of its own."""
    x, wr, bias, wg, wu, wd = _layer()
    share = lambda first, eps=1e-6: moe.routed_experts(
        x, wr, wg[first:first + 8], wu[first:first + 8],
        wd[first:first + 8], 32, first, 4, True, score="sigmoid",
        bias=bias, norm_eps=eps)[0]
    shares = [share(first) for first in (0, 8, 16, 24)]
    whole = _reference(x, wr, bias, wg, wu, wd, 0, 32)
    np.testing.assert_allclose(sum(shares), whole, atol=3e-5)
    np.testing.assert_allclose(shares[2], _reference(x, wr, bias, wg, wu, wd,
                                                     16, 8), atol=3e-5)
    assert float(jnp.max(jnp.abs(whole - shares[0]))) > 1e-2
    # an epsilon that is not the published one is another layer
    assert float(jnp.max(jnp.abs(share(16, 0.25) - shares[2]))) > 1e-2
    np.testing.assert_allclose(share(16, 0.0), shares[2], atol=3e-5)


def test_the_layer_tells_the_op_its_epsilon_and_only_where_it_has_one():
    """``layers.routed_experts(norm_topk_eps=)`` is an attribute of the
    op where given, and no attribute at all where not: the four routed
    programs in the tree lower as they did."""
    def attrs(**kw):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [8, 16], dtype="float32")
            fluid.layers.routed_experts(x, 8, 2, 0, 2, 12, name="moe",
                                        score_func="sigmoid", **kw)
        (op,) = [op for op in main.global_block().ops
                 if op.type == "routed_experts"]
        return op
    assert attrs(norm_topk_eps=1e-6).attr("norm_topk_eps") == 1e-6
    assert attrs().attr("norm_topk_eps", None) is None


# -- the whole small model against the benchmark's reference -------------------

CFG = {"arch": "lfm2", "vocab_size": 96, "num_hidden_layers": 4,
       "num_dense_layers": 1, "hidden_size": 32, "num_attention_heads": 8,
       "num_key_value_heads": 2, "head_dim": 8, "conv_L_cache": 3,
       "layer_types": ["conv", "full_attention", "conv", "conv"] * 6,
       "intermediate_size": 40, "moe_intermediate_size": 24,
       "num_experts": 4, "published": {"num_experts": 8},
       "first_expert": 2, "num_experts_per_tok": 2, "norm_topk_prob": True,
       "routed_scaling_factor": 1, "use_expert_bias": True,
       "rope_theta": 1000000, "norm_eps": 1e-5, "bias_update_rate": 1e-3,
       "embedding_init_std": 0.5, "router_init_std": 0.1}
SEQ = 32


def _drawn(arch, scope):
    """Every norm weight and every selection bias drawn, so that a
    dropped one shows."""
    rng = np.random.RandomState(5)
    for name in scope.local_var_names():
        norm = name.endswith(("_ln1", "_ln2", "_q_norm", "_k_norm",
                              "_final_norm"))
        if norm or name.endswith("_moe.bias"):
            was = np.asarray(scope.find_var(name))
            scope.set(name, jnp.asarray(
                was + rng.randn(*was.shape).astype(np.float32)
                * (0.3 if norm else 0.05)))


@pytest.fixture(scope="module")
def _initialised():
    return small_model_test.initialised("lfm2", CFG, SEQ, _drawn)


@pytest.fixture
def small_model(_initialised):
    """(arch, main, forward, scope, cost, logits, None) as initialised,
    ONCE a file (tests/small_model_test.py)."""
    return small_model_test.as_initialised(*_initialised)


def _batch(rows=2):
    rng = np.random.RandomState(12)
    src = rng.randint(3, 96, (rows, SEQ)).astype(np.int64)
    return {"src": src, "label": np.roll(src, -1, axis=1),
            "mask": (rng.rand(rows, SEQ) > 0.2).astype(np.float32)}


def _regions(program):
    return [op.attr("sub_block").ops for op in program.global_block().ops
            if op.type == "recompute_block"]


def test_small_model_loss_and_logits_are_the_references(small_model):
    """The for_test clone's loss and logits, the routed layers' choices
    fetched from INSIDE their recompute regions in the same run and
    handed to the reference; the stack as the program's ops state it:
    the mixer by the layer's kind, the FFN by its index, the head the
    embedding's own table."""
    arch, main, forward, scope, cost, logits, _ = small_model
    feed = _batch()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        params = arch.params_of_program(main, scope, CFG)
        names = arch.router_choices(forward)
        fetched = exe.run(forward, feed=feed,
                          fetch_list=[cost, logits] + list(names))
        counters = arch.program_counters(main, scope)
    regions = _regions(forward)
    of = lambda ops, kind: [op for op in ops if op.type == kind]
    assert [[len(of(ops, kind)) for kind in (
        "gated_short_conv", "causal_attention", "qk_norm_rope",
        "routed_experts")] for ops in regions] == [
            [1, 0, 0, 0], [0, 1, 2, 1], [1, 0, 0, 1], [1, 0, 0, 1]]
    (attention,) = of(regions[1], "causal_attention")
    assert (attention.attr("n_head"), attention.attr("n_kv_head"),
            attention.attr("window")) == (8, 2, 0)
    for ops in regions[1:]:
        (routed,) = of(ops, "routed_experts")
        assert routed.attr("norm_topk_eps") == 1e-6
        assert routed.attr("score_func") == "sigmoid" and routed.input("Bias")
        assert not routed.attr("shared_expert")
    # the head: a mul against the embedding's own parameter
    last = forward.global_block().ops
    (head,) = [op for op in last if op.type == "mul"
               and op.attr("transpose_Y", False)]
    assert head.input("Y") == ["lfm2_word_emb"]
    got_cost, got_logits, choices = fetched[0], fetched[1], fetched[2:]
    want = jax.jit(lambda p, *batch: arch.lm_loss(p, *batch, CFG))(
        params, feed["src"], feed["label"], feed["mask"])
    np.testing.assert_allclose(got_cost, want, rtol=2e-5)
    assert len(choices) == 3 and choices[0].shape == (2, SEQ, 2)
    assert counters["steps"] == [0] and sum(counters["expert_rows"]) == 0
    assert min(counters["selection_bias_abs_max"]) > 0        # as drawn
    logits_at = jax.jit(lambda p, tokens, chosen=None: arch.logits_at(
        p, tokens, 0, SEQ, CFG, chosen))
    for row in range(2):
        tokens = jnp.asarray(feed["src"][row])
        handed = logits_at(params, tokens,
                           np.stack([c[row:row + 1] for c in choices]))
        assert compare.logits_error(got_logits[row], handed) < 2e-6
        np.testing.assert_allclose(handed, logits_at(params, tokens),
                                   atol=1e-6)


def test_small_model_one_steps_gradients_are_the_references(small_model):
    """SGD at rate 1 turns a step's parameter change into its gradient:
    every parameter's against jax.grad of the reference's loss, through
    the recompute regions; the embedding's is the sum of its use as
    table and as head (the reference's one ``word_emb``); the selection
    bias moves by its step and gets no gradient."""
    arch, main, _, scope, cost, _, _ = small_model
    feed = _batch()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        before = arch.params_of_program(main, scope, CFG)
        exe.run(main, feed=feed, fetch_list=[cost])
        after = arch.params_of_program(main, scope, CFG)
        counters = arch.program_counters(main, scope)
    assert counters["steps"] == [1]
    # three routed layers x 64 rows x top-2, once a step
    assert sum(counters["expert_rows"]) == 3 * 2 * SEQ * 2
    grads = jax.jit(jax.grad(lambda p: arch.lm_loss(
        p, feed["src"], feed["label"], feed["mask"], CFG)))(before)
    moved = jax.tree.map(lambda a, b: a - b, before, after)
    flat_g, _ = jax.tree_util.tree_flatten_with_path(grads)
    # embedding, final norm; a conv layer 3 + 2 norms, the attention
    # layer 6 + 2; the dense FFN 3, a routed one router, bias and 3
    assert len(flat_g) == 2 + 3 * 5 + 8 + 3 + 3 * 5
    for (path, g), m in zip(flat_g, jax.tree.leaves(moved)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):
            assert float(np.max(np.abs(g))) == 0.0, name
            assert set(np.round(np.abs(m) / 1e-3).astype(int).ravel()) \
                <= {0, 1}, name
            continue
        err = float(np.linalg.norm(g - m) / (np.linalg.norm(g) + 1e-12))
        assert err < 1e-4, name


def test_a_conv_layers_output_at_a_row_stands_still_when_later_rows_move(
        small_model):
    """The convolution looks back `conv_L_cache` - 1 rows and never
    forward: with the tokens after row t changed, the first conv
    layer's mixer output (and the stream it leaves) is bit for bit the
    same up to t, and differs after it; the same holds of the whole
    model's logits, the attention layer being causal."""
    arch, main, forward, scope, cost, logits, _ = small_model
    (conv,) = [op for op in _regions(forward)[0]
               if op.type == "gated_short_conv"]
    out = conv.output("Out")[0]
    feed = _batch(rows=1)
    t = 20
    later = {**feed, "src": feed["src"].copy()}
    later["src"][0, t + 1:] = (feed["src"][0, t + 1:] + 7) % 96
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        a = exe.run(forward, feed=feed, fetch_list=[out, logits])
        b = exe.run(forward, feed=later, fetch_list=[out, logits])
    for name, x, y in zip(("the conv operator", "logits"), a, b):
        np.testing.assert_array_equal(x[0, :t + 1], y[0, :t + 1],
                                      err_msg=name)
        assert np.abs(x[0, t + 1:] - y[0, t + 1:]).max() > 1e-3, name
    # and no further back than two rows: row t + 3 of the conv op's
    # output sees rows t + 1 .. t + 3, all moved; a change at row t + 1
    # ALONE reaches rows t + 1 .. t + 3 and not row t + 4
    one = {**feed, "src": feed["src"].copy()}
    one["src"][0, t + 1] = (feed["src"][0, t + 1] + 7) % 96
    with fluid.scope_guard(scope):
        (c,) = exe.run(forward, feed=one, fetch_list=[out])
    moved = np.abs(c[0] - a[0][0]).max(-1) > 0
    assert moved.nonzero()[0].tolist() == [t + 1, t + 2, t + 3]
