"""The mixture-of-experts model whose router reads the layer's input
before attention (ISSUE 46), at small sizes with seeded weights on the
CPU: the expert layer's two new arguments (a router input apart from
the experts', a ReLU gate), where each gradient goes, the shares of a
4-way expert-parallel group adding up to the uncut layer, the published
router against `moe.route`, the old call bit for bit what it was, and
the whole small model against the benchmark's float32 reference
(``chipbench/reference/smallthinker_lm.py``).
"""

import hashlib
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
from chipbench.reference import compare, smallthinker_lm  # noqa: E402


def _r(*shape, seed=0, scale=0.5):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _layer(n=48, d=16, f=12, e=64, seed=80):
    """(x, router_x, router_w, w_gate, w_up, w_down) of one expert layer
    over all `e` experts."""
    return (_r(n, d, seed=seed, scale=1.0), _r(n, d, seed=seed + 1, scale=1.0),
            _r(d, e, seed=seed + 2),
            _r(e, d, f, seed=seed + 3, scale=d ** -0.5),
            _r(e, d, f, seed=seed + 4, scale=d ** -0.5),
            _r(e, f, d, seed=seed + 5, scale=f ** -0.5))


_CFG6 = {"moe_num_active_primary_experts": 6}


def _reference(x, rx, wr, wg, wu, wd, first, held):
    p = {"router": wr, "w_gate": wg[first:first + held],
         "w_up": wu[first:first + held], "w_down": wd[first:first + held]}
    return smallthinker_lm.expert_layer(p, rx, x, _CFG6, first, held,
                                        lambda a, b: a @ b)


# -- the expert layer's two arguments ------------------------------------------

def test_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test. 4 chips holding 16 of 64 experts each
    (`first_expert` 0, 16, 32, 48), softmax top-6, a ReLU gate, the
    router reading a tensor of its own: the program's four shares add
    up to the reference's layer that holds all 64, and each share is
    the reference's own share. Nothing is counted twice: there is no
    shared expert."""
    x, rx, wr, wg, wu, wd = _layer()
    share = lambda first: moe.routed_experts(
        x, wr, wg[first:first + 16], wu[first:first + 16],
        wd[first:first + 16], 64, first, 6, True, router_x=rx,
        activation="relu")[0]
    shares = [share(first) for first in (0, 16, 32, 48)]
    whole = _reference(x, rx, wr, wg, wu, wd, 0, 64)
    np.testing.assert_allclose(sum(shares), whole, atol=3e-5)
    np.testing.assert_allclose(shares[2], _reference(x, rx, wr, wg, wu, wd,
                                                     32, 16), atol=3e-5)
    # a share is a part, and the parts differ
    assert float(jnp.max(jnp.abs(whole - shares[0]))) > 1e-2
    # the other gate, and the router on the experts' own rows, are far
    silu = moe.routed_experts(x, wr, wg, wu, wd, 64, 0, 6, True,
                              router_x=rx)[0]
    own = moe.routed_experts(x, wr, wg, wu, wd, 64, 0, 6, True,
                             activation="relu")[0]
    assert float(jnp.max(jnp.abs(silu - whole))) > 1e-2
    assert float(jnp.max(jnp.abs(own - whole))) > 1e-2
    with pytest.raises(ValueError, match="gate"):
        moe.routed_experts(x, wr, wg, wu, wd, 64, activation="gelu")


def test_softmax_of_the_chosen_logits_is_routes_renormalised_softmax():
    """The published router (`softmax` over the six chosen logits,
    ``moe_primary_router_apply_softmax``) against `moe.route`'s softmax
    over all 64, the six largest, over their sum: the same experts and
    the same weights to float32 rounding."""
    rx, wr = _r(512, 32, seed=3, scale=1.0), _r(32, 64, seed=4)
    r = rx @ wr
    _, w, idx = moe.route(rx, wr, 6, True)
    top_r, top_i = jax.lax.top_k(r, 6)
    np.testing.assert_array_equal(idx, top_i)
    np.testing.assert_allclose(w, jax.nn.softmax(top_r, -1), rtol=2e-6,
                               atol=1e-7)
    dense = smallthinker_lm.router_weights(r, 6)
    np.testing.assert_allclose(
        jnp.take_along_axis(dense, idx, 1), w, rtol=2e-6, atol=1e-7)
    assert float(jnp.max(jnp.abs(jnp.sum(dense, -1) - 1.0))) < 1e-6


def test_each_gradient_goes_where_its_input_stands():
    """The router's weights send their gradient to the router's INPUT
    and the experts theirs to the normed stream: each against a finite
    difference along a random direction, and against the reference's.
    The experts' input gets nothing through the router, and the
    router's input nothing through the experts."""
    x, rx, wr, wg, wu, wd = _layer(n=40, seed=90)
    dy = _r(40, 16, seed=97)
    out = lambda x, rx: moe.routed_experts(
        x, wr, wg[:16], wu[:16], wd[:16], 64, 0, 6, True, router_x=rx,
        activation="relu")[0]
    loss = lambda x, rx: jnp.sum(out(x, rx) * dy)
    dx, drx = jax.jit(jax.grad(loss, (0, 1)))(x, rx)
    want = jax.jit(jax.grad(lambda x, rx: jnp.sum(_reference(
        x, rx, wr, wg, wu, wd, 0, 16) * dy), (0, 1)))(x, rx)
    for name, got, ref in (("dx", dx, want[0]), ("drouter_x", drx, want[1])):
        assert float(jnp.max(jnp.abs(ref))) > 1e-2, name
        np.testing.assert_allclose(got, ref, atol=2e-5, err_msg=name)
    # a central difference of the plain layer along a direction, in
    # float64 and small enough to flip no choice and cross no gate
    v, eps = _r(40, 16, seed=98, scale=1.0), 1e-6
    with jax.enable_x64(True):
        f64 = lambda *a: [jnp.asarray(np.asarray(x), jnp.float64) for x in a]
        x6, rx6, v6, dy6, *ws = f64(x, rx, v, dy, wr, wg, wu, wd)
        plain = lambda x, rx: float(jnp.sum(_reference(x, rx, *ws, 0, 16)
                                            * dy6))
        fd_x = (plain(x6 + eps * v6, rx6) - plain(x6 - eps * v6, rx6)) \
            / (2 * eps)
        fd_rx = (plain(x6, rx6 + eps * v6) - plain(x6, rx6 - eps * v6)) \
            / (2 * eps)
    np.testing.assert_allclose(float(jnp.sum(dx * v)), fd_x, rtol=1e-4)
    np.testing.assert_allclose(float(jnp.sum(drx * v)), fd_rx, rtol=1e-4)
    # with the weights held still the experts' input gets the same dx
    # and the router's input nothing: the two paths do not mix
    held_still = jax.grad(lambda x, rx: jnp.sum(out(
        x, jax.lax.stop_gradient(rx)) * dy), (0, 1))(x, rx)
    np.testing.assert_allclose(held_still[0], dx, atol=1e-6)
    assert float(jnp.max(jnp.abs(held_still[1]))) == 0.0


def test_the_gate_count_is_the_relus_own():
    """`count_gate`: over the pairs on held experts, the hidden units
    with ``w_gate x > 0``, against the same count written out."""
    x, rx, wr, wg, wu, wd = _layer()
    *_, counts, idx, on = moe.routed_experts(
        x, wr, wg[16:32], wu[16:32], wd[16:32], 64, 16, 6, True,
        router_x=rx, activation="relu", count_gate=True)
    chosen = jnp.any(idx[:, :, None] == jnp.arange(64), 1)      # [N, E]
    gate_on = jnp.einsum("nd,edf->nef", x, wg) > 0              # [N, E, f]
    want = int(jnp.sum(gate_on[:, 16:32] & chosen[:, 16:32, None]))
    assert int(on) == want and 0 < want < int(counts[16:32].sum()) * 12


# what `_old_call` gives on this platform, the sha256 of each result's
# bytes: out, dx, drouter_w, dw_gate, dw_up, dw_down. The first is what
# the parent of PR 46 gave and has not moved since; the five gradients
# are those of ISSUE 47's written backward (float32 sums in another
# order: they part from PR 46's by 4e-7 of the largest; PR 46's were
# "4f6035be14475a7e d8669f99d51a40a8 0b0fb3a9e0153f8d 337d65f05f39a532
# 839e7636a24b647f"). Made with `python -c "import
# tests.test_prerouted_moe as t; print(t._digests(t._old_call()))"`; a
# change that MEANS to move them makes them again so.
_PARENTS = ("04c39e883fc83f5b 7f56f398aee2c82d cd89fe580da5935b "
            "c3b1ae3262f1647c eb721410b49215af 73f2276109e7ff13")


def _old_call():
    """The expert layer called as every program before ISSUE 46 called
    it (no router input, the SiLU gate), forward and every gradient."""
    x, _, wr, wg, wu, wd = _layer(n=96, d=32, f=24, seed=46)
    dy = _r(96, 32, seed=47)

    def loss(x, wr, wg, wu, wd):
        out, aux, _, _ = moe.routed_experts(x, wr, wg[:16], wu[:16],
                                            wd[:16], 64, 0, 6, True)
        return jnp.sum(out * dy) + 0.01 * aux, out

    grads, out = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4), has_aux=True))(
        x, wr, wg, wu, wd)
    return (out,) + tuple(grads)


def _digests(arrays):
    return " ".join(hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]
                    for a in arrays)


def test_the_old_call_is_bit_for_bit_the_parents():
    """No router input and ``activation="silu"``: the output
    bit-identical to the parent of PR 46's on a fixed seed and the
    gradients to ISSUE 47's, and the same with both arguments spelt
    out."""
    got = _old_call()
    assert _digests(got) == _PARENTS
    x, _, wr, wg, wu, wd = _layer(n=96, d=32, f=24, seed=46)
    spelt = moe.routed_experts(x, wr, wg[:16], wu[:16], wd[:16], 64, 0, 6,
                               True, router_x=None, activation="silu")[0]
    np.testing.assert_array_equal(jax.jit(lambda: spelt)(), got[0])


def test_the_lowering_says_its_gate_and_whose_rows_the_router_reads():
    x, rx, wr, wg, wu, wd = _layer()
    labels = dict(path="ragged_dot", experts="64", experts_held="16",
                  top_k="6", score="softmax", shared_expert="false",
                  rows="xla")
    mine = dict(labels, activation="relu", router_input="given")
    old = dict(labels, activation="silu", router_input="own")
    was = moe._LOWERINGS.value(**mine), moe._LOWERINGS.value(**old)
    moe.routed_experts(x, wr, wg[:16], wu[:16], wd[:16], 64, 0, 6, True,
                       router_x=rx, activation="relu")
    moe.routed_experts(x, wr, wg[:16], wu[:16], wd[:16], 64, 0, 6, True)
    assert (moe._LOWERINGS.value(**mine), moe._LOWERINGS.value(**old)) == (
        was[0] + 1, was[1] + 1)
    text = str(jax.make_jaxpr(lambda x, rx: moe.routed_experts(
        x, wr, wg[:16], wu[:16], wd[:16], 64, 0, 6, True, router_x=rx,
        activation="relu")[0])(x, rx).pretty_print(name_stack=True))
    assert "route" in text


# -- the whole small model against the benchmark's reference -------------------

CFG = {"arch": "smallthinker", "vocab_size": 96, "num_hidden_layers": 4,
       "hidden_size": 32, "num_attention_heads": 14,
       "num_key_value_heads": 2, "head_dim": 8, "sliding_window_size": 12,
       "sliding_window_layout": [0, 1, 1, 1] * 13,
       "rope_layout": [0, 1, 1, 1] * 13, "moe_ffn_hidden_size": 24,
       "num_experts": 4, "published": {"moe_num_primary_experts": 8},
       "first_expert": 2, "moe_num_active_primary_experts": 2,
       "norm_topk_prob": True, "rope_theta": 1500000, "rms_norm_eps": 1e-6,
       "embedding_init_std": 1.0, "router_init_std": 0.02}
SEQ = 32


@pytest.fixture(scope="module")
def _initialised():
    return small_model_test.initialised("smallthinker", CFG, SEQ)


@pytest.fixture
def small_model(_initialised):
    """(arch, main, forward, scope, cost, logits) as
    initialised, ONCE a file (tests/small_model_test.py)."""
    return small_model_test.as_initialised(*_initialised)


@pytest.fixture(params=["float32", "bf16_amp"])
def precision(request):
    """(the tolerances of logits as a share of the largest, of the loss,
    of a gradient's error as a share of its norm) under float32 and
    under bf16 AMP, which is on until the test is left. A row that bf16
    sends to another expert than float32 would, or a gate it tips over
    zero, moves a gradient of this size by a row's share: read 0.09 at
    most (a layer's w_gate), 0.03 to 0.05 elsewhere."""
    if request.param == "float32":
        yield 2e-6, 2e-5, 1e-4
        return
    fluid.amp.enable_amp()
    try:
        yield 2e-2, 2e-3, 0.2
    finally:
        fluid.amp.enable_amp(False)


def _batch(rows=2):
    rng = np.random.RandomState(12)
    src = rng.randint(3, 96, (rows, SEQ)).astype(np.int64)
    return {"src": src, "label": np.roll(src, -1, axis=1),
            "mask": (rng.rand(rows, SEQ) > 0.2).astype(np.float32)}


def test_small_model_loss_and_logits_are_the_references(small_model,
                                                        precision):
    """The for_test clone's loss and logits, with every layer's choices
    fetched from INSIDE its recompute region in the same run and handed
    to the reference; the stack's kinds as the program's ops state
    them: a router input on every expert layer, rope on the window
    layers alone."""
    arch, main, forward, scope, cost, logits = small_model
    logits_tol, loss_tol, _ = precision
    feed = _batch()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        params = arch.params_of_program(main, scope, CFG)
        names = arch.router_choices(forward)
        fetched = exe.run(forward, feed=feed,
                          fetch_list=[cost, logits] + list(names))
        counters = arch.program_counters(main, scope)
    regions = [op.attr("sub_block").ops for op in forward.global_block().ops
               if op.type == "recompute_block"]
    assert len(regions) == 4
    of = lambda ops, kind: [op for op in ops if op.type == kind]
    assert [of(ops, "causal_attention")[0].attr("window")
            for ops in regions] == [0, 12, 12, 12]
    assert [len(of(ops, "rope")) for ops in regions] == [0, 2, 2, 2]
    assert not any(of(ops, "qk_norm_rope") for ops in regions)
    for ops in regions:
        (routed,) = of(ops, "routed_experts")
        (first_norm,) = [op for op in of(ops, "rms_norm")
                         if op.input("X") == routed.input("RouterX")]
        assert routed.attr("activation") == "relu"
        # the router reads what the layer's FIRST norm reads, the
        # experts the second norm's output
        assert ops.index(first_norm) == 0
        assert routed.input("X") == of(ops, "rms_norm")[1].output("Out")
    got_cost, got_logits, choices = fetched[0], fetched[1], fetched[2:]
    want = jax.jit(lambda p, *batch: arch.lm_loss(p, *batch, CFG))(
        params, feed["src"], feed["label"], feed["mask"])
    np.testing.assert_allclose(got_cost, want, rtol=loss_tol)
    assert len(choices) == 4 and choices[0].shape == (2, SEQ, 2)
    # a for_test run counts nothing
    assert counters["steps"] == [0] and sum(counters["expert_rows"]) == 0
    assert counters["expert_gate_units"] == [0.0]
    logits_at = jax.jit(lambda p, tokens, chosen=None: arch.logits_at(
        p, tokens, 0, SEQ, CFG, chosen))
    for row in range(2):
        handed = logits_at(params, jnp.asarray(feed["src"][row]),
                           np.stack([c[row:row + 1] for c in choices]))
        assert compare.logits_error(got_logits[row], handed) < logits_tol
        if logits_tol < 1e-4:       # float32 chooses as the reference
            ref = logits_at(params, jnp.asarray(feed["src"][row]))
            np.testing.assert_allclose(handed, ref, atol=1e-6)


def test_small_model_one_steps_gradients_are_the_references(small_model,
                                                            precision):
    """SGD at rate 1 turns a step's parameter change into its gradient:
    every parameter's against jax.grad of the reference's loss, through
    the recompute regions (the router's input is a region's own input);
    the counts, the gate's counter and the step counter move ONCE a
    step."""
    arch, main, _, scope, cost, _ = small_model
    grad_tol = precision[2]
    feed = _batch()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        before = arch.params_of_program(main, scope, CFG)
        exe.run(main, feed=feed, fetch_list=[cost])
        after = arch.params_of_program(main, scope, CFG)
        counters = arch.program_counters(main, scope)
    assert counters["steps"] == [1]
    # four layers x 64 rows x top-2, once a step
    rows = counters["expert_rows"]
    assert sum(rows) == 4 * 2 * SEQ * 2
    units = sum(rows[2:6]) * CFG["moe_ffn_hidden_size"]
    assert counters["expert_gate_units"] == [float(units)]
    assert 0.2 * units < counters["expert_gate_active"][0] < 0.8 * units
    grads = jax.jit(jax.grad(lambda p: arch.lm_loss(
        p, feed["src"], feed["label"], feed["mask"], CFG)))(before)
    moved = jax.tree.map(lambda a, b: a - b, before, after)
    flat_g, _ = jax.tree_util.tree_flatten_with_path(grads)
    # embedding, final norm, head; a layer: 6 of attention and norms,
    # the router and 3 of experts
    assert len(flat_g) == 3 + 4 * 10
    for (path, g), m in zip(flat_g, jax.tree.leaves(moved)):
        err = float(np.linalg.norm(g - m) / (np.linalg.norm(g) + 1e-12))
        assert err < grad_tol, jax.tree_util.keystr(path)
    # the router's weights moved: their gradient came through the
    # layer's input and not through the normed stream
    for layer in jax.tree.leaves(moved["layers"][0]["router"]):
        assert float(np.max(np.abs(layer))) > 0
