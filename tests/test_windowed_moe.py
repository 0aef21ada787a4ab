"""The window-and-full mixture-of-experts model on a window bound in
the flash kernels (ISSUE 38), at small sizes with seeded weights on the
CPU (the windowed kernels themselves: tests/test_flash_window.py): the
ops "causal_attention" and "sigmoid_mul" and ``qk_norm_rope`` without
its rotation, the shares of a 16-way expert-parallel group adding up to
the uncut layer, and the whole small model against the benchmark's
float32 reference (``chipbench/reference/afmoe_lm.py``).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import small_model_test
from paddle_tpu.ops import causal_attention as CA
from paddle_tpu.ops import rotary
from paddle_tpu.parallel import moe

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference import afmoe_lm  # noqa: E402
from flash_test import _band_inputs, _band_written_out  # noqa: E402


def _r(*shape, seed=0, scale=0.5):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


# -- the ops --------------------------------------------------------------------

def test_the_ops_scope_their_kernels_and_leave_the_rotation_out():
    """`causal_attention` names the kind of its layer under its op's
    scope (`window` / `full`); `qk_norm_rope` with `rotate` false is the
    QK-norm alone through `ops/rotary.norm_rope`; `sigmoid_mul` gates in
    float32 and hands back x's dtype."""
    h, hkv, d, t = 4, 2, 32, 64
    q, k, v, _ = _band_inputs(t, h, hkv, d, jnp.float32, seed=9)
    for window, scope in ((16, "window"), (0, "full")):
        text = str(jax.make_jaxpr(lambda q, k, v: CA.causal_attention(
            q, k, v, h, hkv, window))(q, k, v).pretty_print(
                name_stack=True))
        assert scope in text
        np.testing.assert_allclose(
            CA.causal_attention(q, k, v, h, hkv, window),
            _band_written_out(q, k, v, h, hkv, window or t), atol=2e-6)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [t, h * d], dtype="float32")
        g = fluid.layers.data("g", [t, h * d], dtype="float32")
        normed = fluid.layers.qk_norm_rope(x, h, rotate=False)
        turned = fluid.layers.qk_norm_rope(x, h)
        gated = fluid.layers.sigmoid_mul(x, g)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        got = exe.run(main, feed={"x": np.asarray(q), "g": np.asarray(k[
            ..., :1].repeat(h * d, -1))}, fetch_list=[normed, turned, gated])
    ones = jnp.ones(d)
    np.testing.assert_allclose(got[0], rotary.norm_rope(q, ones, h),
                               atol=1e-6)
    np.testing.assert_allclose(
        got[1], rotary.norm_rope(q, ones, h, 10000.0), atol=1e-6)
    assert float(np.max(np.abs(got[0] - got[1]))) > 0.1
    np.testing.assert_allclose(
        got[2], q * jax.nn.sigmoid(k[..., :1]), atol=1e-6)


# -- the shares ------------------------------------------------------------------

def test_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """16 chips holding 2 of 32 experts each, sigmoid top-4 with
    `route_scale`: the program's routed outputs of the sixteen shares
    plus ONE shared expert equal the reference's layer that holds all
    32, and each share is the reference's own share."""
    n, d, f, e, k_top, held = 48, 16, 12, 32, 4, 2
    x, wr = _r(n, d, seed=70, scale=1.0), _r(d, e, seed=71)
    wg, wu = (_r(e, d, f, seed=s, scale=d ** -0.5) for s in (72, 73))
    wd = _r(e, f, d, seed=74, scale=f ** -0.5)
    shared = tuple(_r(*shape, seed=s, scale=0.3) for shape, s in (
        ((d, f), 75), ((d, f), 76), ((f, d), 77)))
    cfg = {"num_experts_per_tok": k_top, "published": {"num_experts": e},
           "route_norm": True, "route_scale": 2.826}
    mm = lambda a, b: a @ b

    def reference(first, count, with_shared):
        p = {"router": wr, "bias": jnp.zeros(e), "shared": shared,
             "w_gate": wg[first:first + count],
             "w_up": wu[first:first + count],
             "w_down": wd[first:first + count]}
        return afmoe_lm.expert_layer(p, x, cfg, first, count, mm,
                                     shared=with_shared)

    routed = lambda first: moe.routed_experts(
        x, wr, wg[first:first + held], wu[first:first + held],
        wd[first:first + held], e, first, k_top, True, score="sigmoid",
        scaling=2.826, shared_expert=True)[0]
    once = (jax.nn.silu(x @ shared[0]) * (x @ shared[1])) @ shared[2]
    shares = [routed(first) for first in range(0, e, held)]
    assert len(shares) == 16
    np.testing.assert_allclose(once + sum(shares), reference(0, e, True),
                               atol=3e-5)
    np.testing.assert_allclose(shares[3], reference(6, held, False),
                               atol=3e-5)
    # the shared expert once, and not a sixteenth of it, nor sixteen
    assert float(jnp.max(jnp.abs(once))) > 1e-2


# -- the whole small model against the benchmark's reference -------------------

CFG = {"arch": "afmoe", "vocab_size": 96, "num_hidden_layers": 4,
       "num_dense_layers": 1, "hidden_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 12,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention"],
       "intermediate_size": 40, "moe_intermediate_size": 24,
       "num_experts": 4, "published": {"num_experts": 8}, "first_expert": 2,
       "num_experts_per_tok": 2, "num_shared_experts": 1,
       "route_norm": True, "route_scale": 2.826,
       "load_balance_coeff": 1e-3, "rope_theta": 10000,
       "rms_norm_eps": 1e-5, "embedding_init_std": 0.02,
       "router_init_std": 0.1}
SEQ = 32


@pytest.fixture(scope="module")
def _initialised():
    return small_model_test.initialised("afmoe", CFG, SEQ)


@pytest.fixture
def small_model(_initialised):
    """(arch, main, forward, scope, cost, logits) as
    initialised, ONCE a file (tests/small_model_test.py)."""
    return small_model_test.as_initialised(*_initialised)


def _batch(rows=2):
    rng = np.random.RandomState(12)
    src = rng.randint(3, 96, (rows, SEQ)).astype(np.int64)
    return {"src": src, "label": np.roll(src, -1, axis=1),
            "mask": (rng.rand(rows, SEQ) > 0.2).astype(np.float32)}


def test_small_model_loss_and_logits_are_the_references(small_model):
    """The for_test clone's loss and logits, with every routed layer's
    choices fetched from INSIDE its recompute region in the same run;
    the stack's kinds as the program's ops state them."""
    arch, main, forward, scope, cost, logits = small_model
    feed = _batch()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        params = arch.params_of_program(main, scope, CFG)
        names = arch.router_choices(forward)
        fetched = exe.run(forward, feed=feed,
                          fetch_list=[cost, logits] + list(names))
        counters = arch.program_counters(main, scope)
    ops = arch._ops(forward)
    assert [op.attr("window") for op in ops
            if op.type == "causal_attention"] == [12, 12, 0, 12]
    assert [op.attr("rotate", True) for op in ops
            if op.type == "qk_norm_rope"] == [True, True, True, True, False,
                                              False, True, True]
    assert sum(op.type == "rms_norm" for op in ops) == 4 * 4 + 1
    assert sum(op.type == "sigmoid_mul" for op in ops) == 4
    got_cost, got_logits, choices = fetched[0], fetched[1], fetched[2:]
    # the reference as ONE program each: eagerly it is a hundred small
    # compilations, more seconds than the model under test takes
    want = jax.jit(lambda p, *batch: arch.lm_loss(p, *batch, CFG))(
        params, feed["src"], feed["label"], feed["mask"])
    np.testing.assert_allclose(got_cost, want, rtol=2e-5)
    assert len(choices) == 3 and choices[0].shape == (2, SEQ, 2)
    # a for_test run counts nothing and moves no bias
    assert counters["steps"] == [0] and sum(counters["expert_rows"]) == 0
    logits_at = jax.jit(lambda p, tokens, chosen=None: arch.logits_at(
        p, tokens, 0, SEQ, CFG, chosen))
    for row in range(2):
        ref = logits_at(params, jnp.asarray(feed["src"][row]))
        np.testing.assert_allclose(got_logits[row], ref, atol=3e-5)
        handed = logits_at(params, jnp.asarray(feed["src"][row]),
                           np.stack([c[row:row + 1] for c in choices]))
        np.testing.assert_allclose(handed, ref, atol=1e-6)


def test_small_model_one_steps_gradients_are_the_references(small_model):
    """SGD at rate 1 turns a step's parameter change into its gradient:
    every parameter's against jax.grad of the reference's loss, through
    the recompute regions; the counts, the step counter and the
    selection bias move ONCE a step."""
    arch, main, _, scope, cost, _ = small_model
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
    for layer in after["layers"][1:]:
        np.testing.assert_allclose(np.abs(layer["bias"]), 1e-3, rtol=1e-5)

    def floats(p):
        return {**p, "layers": [{k: v for k, v in layer.items()
                                 if k != "bias"} for layer in p["layers"]]}

    def loss(p):
        whole = {**p, "layers": [
            {**layer, **({"bias": was["bias"]} if "bias" in was else {})}
            for layer, was in zip(p["layers"], before["layers"])]}
        return arch.lm_loss(whole, feed["src"], feed["label"], feed["mask"],
                            CFG)

    grads = jax.jit(jax.grad(loss))(floats(before))
    moved = jax.tree.map(lambda a, b: a - b, floats(before), floats(after))
    flat_g, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_m = jax.tree.leaves(moved)
    # embedding, final norm, head; a layer: 11 of attention and norms,
    # 3 of the dense FFN or 3 + 4 of experts
    assert len(flat_g) == 3 + 4 * 11 + 3 + 3 * 7
    for (path, g), m in zip(flat_g, flat_m):
        scale = float(np.max(np.abs(g))) + 1e-8
        assert float(np.max(np.abs(g - m))) / scale < 2e-3, \
            jax.tree_util.keystr(path)
