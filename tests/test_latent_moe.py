"""Latent attention, hyper-connections and the sigmoid-routed expert
layer with a shared expert (ISSUE 34), at small sizes with seeded
weights on the CPU: the two-part flash kernels in interpret mode
against dense float32 math, Sinkhorn-Knopp and its gradient, the
router's selection bias, the shares of an expert-parallel group adding
up to the uncut layer, the recompute region's counters, and the whole
small model against the benchmark's float32 reference
(``chipbench/reference/xing_lm.py``). The ops: "mla_attention",
"hyper_connection" and the new inputs of "routed_experts".
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import small_model_test
from flash_test import _with_grads
from paddle_tpu.ops import flash_attention as FA
from paddle_tpu.ops import hyper_connection as HC
from paddle_tpu.ops import latent_attention as LA
from paddle_tpu.ops import rotary
from paddle_tpu.parallel import moe

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference import xing_lm  # noqa: E402


def _r(*shape, seed=0, scale=0.5):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


# -- a score of two parts through the flash kernels -----------------------------

def _two_parts(b, h, t, d, d2, seed=0):
    return (_r(b, t, h * d, seed=seed, scale=0.3),
            _r(b, t, h * d, seed=seed + 1, scale=0.3),
            _r(b, t, h * d, seed=seed + 2),
            _r(b, t, h * d2, seed=seed + 3, scale=0.3),
            _r(b, t, d2, seed=seed + 4, scale=0.3))


@functools.partial(jax.jit, static_argnums=(5, 6))
def _dense_two_parts(q, k, v, q2, k2, h, scale):
    """softmax((q k^T + q2 k2^T) scale) v written out, a head at a
    time, k2 the one key every head reads (ONE program: eagerly an op
    at a time is compiled)."""
    b, t, _ = q.shape
    heads = lambda x: x.reshape(b, t, h, -1).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", heads(q), heads(k)) \
        + jnp.einsum("bhqd,bkd->bhqk", heads(q2), k2)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s * scale, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), heads(v))
    return out.transpose(0, 2, 1, 3).reshape(b, t, -1)


@pytest.mark.parametrize("t,d2,block", [
    (256, 64, 128), (256, 64, None), (512, 32, 256), (256, 128, 128)],
    ids=["streamed_192", "one_block_192", "four_heads_to_a_tile",
         "whole_tile_part"])
def test_two_part_kernels_against_dense_float32(t, d2, block):
    """Key width 128 + d2, value width 128: out, dq_nope, dk_nope, dv,
    dq_pe and dk_pe (summed over the heads) of the kernels in interpret
    mode against the dense math written out."""
    h, d = 4, 128
    args = _two_parts(2, h, t, d, d2)
    w = _r(2, t, h * d, seed=9)
    scale = (d + d2) ** -0.5 * 1.3

    def kernels(*a):
        return FA.flash_bthd(a[0], a[1], a[2], h, causal=True, scale=scale,
                             force="interpret", block_q=block, block_k=block,
                             q2=a[3], k2=a[4])

    # each side's output and five gradients as ONE program
    (o_got, got), (o_want, want) = (
        jax.jit(_with_grads(f, lambda o: jnp.sum(o * w)))(*args)
        for f in (kernels, lambda *a: _dense_two_parts(*a, h, scale)))
    np.testing.assert_allclose(o_got, o_want, atol=2e-6)
    for name, g, r in zip(("dq_nope", "dk_nope", "dv", "dq_pe", "dk_pe"),
                          got, want):
        assert g.shape == r.shape
        g, r = np.asarray(g), np.asarray(r)
        err = float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
        assert err < 1e-5, (name, err)


def test_one_part_alone_is_todays_kernel():
    """A second part of zeros adds exact zeros to every score: out and
    the first part's gradients are those of the one-part kernels, and
    with no second part handed nothing of the two-part path runs (the
    counter says which)."""
    h, d, t = 2, 128, 256
    q, k, v, q2, k2 = _two_parts(1, h, t, d, 64, seed=20)
    scale = d ** -0.5
    one = lambda q, k, v: FA.flash_bthd(
        q, k, v, h, causal=True, scale=scale, force="interpret",
        block_q=128, block_k=128)
    two = lambda q, k, v: FA.flash_bthd(
        q, k, v, h, causal=True, scale=scale, force="interpret",
        block_q=128, block_k=128, q2=jnp.zeros_like(q2), k2=k2)
    np.testing.assert_array_equal(one(q, k, v), two(q, k, v))
    # both streamed backwards are ONE kernel (the one-part one since
    # ISSUE 39, the two-part one since ISSUE 56) on grids of their own:
    # the same sums in another order, held to the float32 tolerance of
    # this file's other gradient tests
    for a, b in zip(jax.grad(lambda *x: one(*x).sum(), (0, 1, 2))(q, k, v),
                    jax.grad(lambda *x: two(*x).sum(), (0, 1, 2))(q, k, v)):
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) < 1e-5
    count = FA._LOWERINGS
    labels = dict(path="interpret", entry="bthd", heads_per_block="1",
                  backward="fused_streamed", mask="causal", kv_groups="1",
                  key_width="192", value_width="128", second_part="shared",
                  window="0")
    was = count.value(**labels)
    FA.flash_bthd(q, k, v, h, causal=True, force="interpret", q2=q2, k2=k2)
    assert count.value(**labels) == was + 1
    plain = dict(labels, backward="fused", key_width="128",
                 second_part="none", window="0")
    was = count.value(**plain)
    FA.flash_bthd(q, k, v, h, causal=True, force="interpret")
    assert count.value(**plain) == was + 1


def test_what_the_two_part_kernels_cannot_take_goes_dense():
    """Heads that fill no whole lane tile, or a second part that
    divides no tile: dense math, the same numbers."""
    q, k, v, q2, k2 = _two_parts(1, 4, 128, 64, 32, seed=30)
    out = FA.flash_bthd(q, k, v, 4, causal=True, force="interpret",
                        q2=q2, k2=k2)
    np.testing.assert_allclose(out, _dense_two_parts(
        q, k, v, q2, k2, 4, 96 ** -0.5), atol=2e-6)
    with pytest.raises(ValueError):
        FA.flash_bthd(q, k, v, 4, q2=q2, k2=k2[..., :16])


@pytest.mark.parametrize("on_a_tpu", [False, True],
                         ids=["jax_numpy", "as_a_tpu_dispatches"])
def test_mla_attention_op_turns_both_rotary_parts(on_a_tpu, monkeypatch):
    """The op against the reference's own pieces: YaRN's frequencies,
    rotate-half on q_pe and on the one k_pe, the two-part score. As a
    TPU dispatches (ISSUE 50; the kernel in interpret mode where the
    shape alone would take it): the query's part, heads of 64 two to a
    lane tile, takes the kernel pair of ``ops/rotary.py``, and the ONE
    key head of 64, half a tile, keeps the jax.numpy form."""
    if on_a_tpu:
        resolve = rotary._resolve_path
        monkeypatch.setattr(rotary, "_on_tpu", lambda x: True)
        monkeypatch.setattr(
            rotary, "_resolve_path", lambda *args: resolve(*args).replace(
                "pallas", "interpret"))
    count = lambda path, heads: rotary._LOWERINGS.value(
        path=path, heads=str(heads), head_dim="64", norm="false",
        rotate="true")
    paths = [("interpret" if on_a_tpu else "xla", 2), ("xla", 1)]
    before = [count(*labels) for labels in paths]
    cfg = {"qk_rope_head_dim": 64, "rope_theta": 10000,
           "qk_nope_head_dim": 128,
           "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                            "mscale": 1, "mscale_all_dim": 1,
                            "original_max_position_embeddings": 4096}}
    freqs = rotary.yarn_inv_freq(64, 10000.0, 64, 4096, 32, 1)
    np.testing.assert_allclose(freqs, xing_lm.yarn_frequencies(cfg),
                               rtol=1e-6)
    # the fastest pairs turn as without YaRN, the slowest 64 times slower
    assert freqs[0] == 1.0 and freqs[-1] == pytest.approx(
        10000 ** (-62 / 64) / 64)
    scale = xing_lm.softmax_scale(cfg)
    assert scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    h, t = 2, 128
    q, k, v, q2, k2 = _two_parts(1, h, t, 128, 64, seed=40)
    got = LA.mla_attention(q, q2, k, k2, v, h, freqs, scale,
                           force="interpret")
    turned_q = xing_lm._rope(q2[0].reshape(t, h, 64), jnp.asarray(freqs))
    turned_k = xing_lm._rope(k2[0], jnp.asarray(freqs))
    want = _dense_two_parts(q, k, v, turned_q.reshape(1, t, h * 64),
                            turned_k[None], h, scale)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert [count(*labels) for labels in paths] == [n + 1 for n in before]


# -- Sinkhorn-Knopp and the hyper-connection -----------------------------------

def test_sinkhorn_is_doubly_stochastic_and_differentiable():
    """Rows and columns of SK(exp(.)) sum to 1 within 1e-5 after 20
    rounds, the program's [n, n, N] form and the reference's [N, n, n]
    agree, and the gradient matches central differences."""
    raw = np.random.RandomState(3).randn(5, 4, 4)
    raw[0] = 4.0 * np.eye(4)               # the initial bias's case
    got = HC.sinkhorn(jnp.exp(jnp.asarray(raw, jnp.float32)).transpose(
        1, 2, 0), 20, 1e-6)
    np.testing.assert_allclose(got.sum(0), 1.0, atol=1e-5)
    np.testing.assert_allclose(got.sum(1), 1.0, atol=1e-5)
    np.testing.assert_allclose(
        got.transpose(2, 0, 1),
        xing_lm.sinkhorn(jnp.exp(jnp.asarray(raw, jnp.float32)), 20, 1e-6),
        atol=1e-6)
    assert float(got[:, :, 0].trace()) > 3.6   # starts near the identity
    with jax.enable_x64():
        w = np.random.RandomState(4).randn(4, 4, 5)
        x64 = jnp.asarray(raw.transpose(1, 2, 0), jnp.float64)
        f = lambda x: jnp.sum(HC.sinkhorn(jnp.exp(x), 20, 1e-6) * w)
        grad = jax.grad(f)(x64)
        for at in ((0, 0, 0), (1, 2, 3), (3, 1, 4)):
            step = jnp.zeros_like(x64).at[at].set(1e-5)
            fd = (f(x64 + step) - f(x64 - step)) / 2e-5
            assert float(grad[at]) == pytest.approx(float(fd), rel=1e-5,
                                                    abs=1e-9)


def test_hyper_connection_is_the_references():
    """Coefficients, H_pre X and H_res X + H_post^T y of the program's
    flat [N, n d] stream against the reference's [N, n, d] einsums."""
    n, d, rows = 4, 8, 6
    cfg = {"hc_mult": n, "rms_norm_eps": 1e-6, "hc_sinkhorn_iters": 20,
           "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
           "mhc_h_res_clamp_max": 30}
    x = _r(rows, n * d, seed=5, scale=1.0)
    p = {"proj": _r(n * d, n * (n + 2), seed=6, scale=0.3),
         "alpha": jnp.asarray([0.5, 0.7, 0.9]),
         "bias": _r(n * (n + 2), seed=7)}
    sub = lambda h: jnp.tanh(h) * 2.0 + 1.0
    pre, post, res = HC.coefficients(x, p["proj"], p["alpha"], p["bias"], n,
                                     20, 1e-6, (-30.0, 30.0))
    got = HC.merge(x, post, res, sub(HC.mix_in(x, pre, n)), n)
    want = xing_lm.hyper_connection(x.reshape(rows, n, d), p, sub, cfg)
    np.testing.assert_allclose(got.reshape(rows, n, d), want, atol=2e-6)
    # H_post = 2 sigmoid: a zero pre-activation gives every lane the
    # sublayer's output once
    flat = HC.coefficients(x, p["proj"] * 0, p["alpha"], p["bias"] * 0, n,
                           20, 1e-6, (-30.0, 30.0))
    np.testing.assert_allclose(flat[1], 1.0)
    np.testing.assert_allclose(flat[0], 0.5)
    np.testing.assert_allclose(flat[2], 0.25, atol=1e-5)


def test_hyper_connection_counter():
    """One count a stage lowered, by the path it took: d 8 is no whole
    lane tile, so both stages take the jax.numpy form."""
    mixed = dict(lanes="4", sinkhorn_iters="20", path="xla", stage="mix")
    merged = dict(lanes="4", sinkhorn_iters="", path="xla", stage="merge")
    was = HC._LOWERINGS.value(**mixed), HC._LOWERINGS.value(**merged)
    x = _r(3, 32)
    h, post, res, through = HC.mix_stage(
        x, _r(32, 24), jnp.ones(3), jnp.zeros(24), 4, 20, 1e-6,
        (-30.0, 30.0))
    assert through is x
    HC.merge_stage(through, post, res, h, 4)
    assert (HC._LOWERINGS.value(**mixed),
            HC._LOWERINGS.value(**merged)) == (was[0] + 1, was[1] + 1)


# -- the sigmoid router, its selection bias, and the shares ---------------------

N, D, F, E, K = 48, 16, 12, 16, 4


def _experts(seed=0):
    return (_r(N, D, seed=seed, scale=1.0), _r(D, E, seed=seed + 1),
            _r(E, D, F, seed=seed + 2, scale=D ** -0.5),
            _r(E, D, F, seed=seed + 3, scale=D ** -0.5),
            _r(E, F, D, seed=seed + 4, scale=F ** -0.5))


def test_sigmoid_router_bias_moves_the_choice_and_never_the_weights():
    x, wr = _experts(seed=50)[:2]
    scores, weight, chosen = moe.route(x, wr, K, True, score="sigmoid",
                                       scaling=2.0)
    np.testing.assert_allclose(scores, jax.nn.sigmoid(x @ wr), rtol=1e-5)
    np.testing.assert_allclose(weight.sum(-1), 2.0, rtol=1e-6)
    # a bias that lifts expert 3 over everything puts it in every row's
    # choice; its weight is still its own score's share
    bias = jnp.zeros(E).at[3].set(10.0)
    _, w_b, c_b = moe.route(x, wr, K, True, score="sigmoid", bias=bias,
                            scaling=2.0)
    assert bool(jnp.all(jnp.any(c_b == 3, axis=1)))
    assert not bool(jnp.all(jnp.any(chosen == 3, axis=1)))
    picked = jnp.take_along_axis(scores, c_b, 1)
    np.testing.assert_allclose(
        w_b, 2.0 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w_b.sum(-1), 2.0, rtol=1e-6)
    # a zero bias chooses as no bias does, and the bias has no gradient
    _, w_0, c_0 = moe.route(x, wr, K, True, score="sigmoid",
                            bias=jnp.zeros(E), scaling=2.0)
    np.testing.assert_array_equal(c_0, chosen)
    np.testing.assert_allclose(w_0, weight, rtol=1e-6)
    grad = jax.grad(lambda b: moe.route(x, wr, K, True, score="sigmoid",
                                        bias=b)[1].sum())(bias)
    np.testing.assert_array_equal(grad, 0.0)
    # the update's sign: up where an expert took fewer rows than the mean
    counts = jnp.asarray([0, 9, 3, 3] * 4)
    np.testing.assert_allclose(
        moe.bias_step(jnp.zeros(E), counts, 1e-3),
        1e-3 * np.sign(3.75 - np.asarray(counts)))


def _dense_layer(x, wr, wg, wu, wd, first, held):
    """sum_e w_e E_e(x) over the chosen experts in [first, first+held),
    every expert on every row."""
    _, weight, chosen = moe.route(x, wr, K, True, score="sigmoid",
                                  scaling=2.0)
    out = jnp.zeros_like(x)
    for e in range(first, first + held):
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=1)
        y = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
        out = out + w_e[:, None] * y
    return out


def test_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """8 chips holding 2 of 16 experts each: their routed outputs add
    up to the layer that holds all 16, and the shared expert, which
    every chip holds whole, is counted once."""
    x, wr, wg, wu, wd = _experts(seed=60)
    shared = lambda x: (jax.nn.silu(x @ wg[0].T[:F].T) * (x @ wu[1])) @ wd[2]
    routed = lambda first, held: moe.routed_experts(
        x, wr, wg[first:first + held], wu[first:first + held],
        wd[first:first + held], E, first, K, True, score="sigmoid",
        scaling=2.0, shared_expert=True)[0]
    whole = shared(x) + routed(0, E)
    shares = sum(routed(first, 2) for first in range(0, E, 2))
    np.testing.assert_allclose(shared(x) + shares, whole, atol=2e-5)
    np.testing.assert_allclose(routed(4, 2), _dense_layer(
        x, wr, wg, wu, wd, 4, 2), atol=2e-5)
    assert float(jnp.max(jnp.abs(whole - shares))) > 1e-2   # once, not never
    labels = dict(path="ragged_dot", experts=str(E), experts_held="2",
                  top_k=str(K), score="sigmoid", shared_expert="true",
                  rows="xla", activation="silu", router_input="own")
    assert moe._LOWERINGS.value(**labels) >= 9


# -- the whole small model against the benchmark's reference -------------------

CFG = {"arch": "xing", "vocab_size": 96, "num_hidden_layers": 3,
       "first_k_dense_replace": 1, "hidden_size": 32,
       "num_attention_heads": 2, "q_lora_rank": 24, "kv_lora_rank": 16,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "intermediate_size": 40, "moe_intermediate_size": 24,
       "n_routed_experts": 4, "published": {"n_routed_experts": 8},
       "first_expert": 2, "num_experts_per_tok": 2, "n_shared_experts": 1,
       "norm_topk_prob": True, "routed_scaling_factor": 2,
       "bias_update_rate": 1e-3, "hc_mult": 4, "hc_sinkhorn_iters": 3,
       "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
       "mhc_h_res_clamp_max": 30, "rope_theta": 10000,
       "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                        "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 4096,
                        "type": "yarn"},
       "rms_norm_eps": 1e-6, "embedding_init_std": 1.0}
SEQ = 32


@pytest.fixture(scope="module")
def _initialised():
    return small_model_test.initialised("xing", CFG, SEQ)


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
    choices fetched from INSIDE its recompute region in the same run."""
    arch, main, forward, scope, cost, logits = small_model
    feed = _batch()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        params = arch.params_of_program(main, scope, CFG)
        names = arch.router_choices(forward)
        fetched = exe.run(forward, feed=feed,
                          fetch_list=[cost, logits] + list(names))
        counters = arch.program_counters(main, scope)
    assert sum(op.type == "recompute_block"
               for op in forward.global_block().ops) == 3
    got_cost, got_logits, choices = fetched[0], fetched[1], fetched[2:]
    # the reference as ONE program each: eagerly it is some 150 small
    # compilations, more seconds than the model under test takes
    want = jax.jit(lambda p, *batch: arch.lm_loss(p, *batch, CFG))(
        params, feed["src"], feed["label"], feed["mask"])
    np.testing.assert_allclose(got_cost, want, rtol=2e-5)
    assert len(choices) == 2 and choices[0].shape == (2, SEQ, 2)
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
    the recompute regions. The regions run twice a step (forward and
    rematerialised) and the counts, the step counter and the selection
    bias move ONCE."""
    arch, main, _, scope, cost, _ = small_model
    feed = _batch()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        before = arch.params_of_program(main, scope, CFG)
        exe.run(main, feed=feed, fetch_list=[cost])
        after = arch.params_of_program(main, scope, CFG)
        counters = arch.program_counters(main, scope)
        exe.run(main, feed=feed, fetch_list=[cost])
        twice = arch.program_counters(main, scope)
    assert counters["steps"] == [1] and twice["steps"] == [2]
    # two routed layers x 64 rows x top-2, once a step
    assert sum(counters["expert_rows"]) == 2 * 2 * SEQ * 2
    assert sum(twice["expert_rows"]) == 2 * sum(counters["expert_rows"])
    for layer in after["layers"][1:]:
        np.testing.assert_allclose(np.abs(layer["bias"]), 1e-3, rtol=1e-5)
    assert twice["selection_bias_abs_max"][0] <= 2e-3 + 1e-9

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
    # embedding, final norm, head; a layer: 12 of attention and norms,
    # 2 x 3 of hyper-connections, 3 of the dense FFN or 3 + 4 of experts
    assert len(flat_g) == 3 + 3 * 18 + 3 + 2 * 7
    for (path, g), m in zip(flat_g, flat_m):
        scale = float(np.max(np.abs(g))) + 1e-8
        assert float(np.max(np.abs(g - m))) / scale < 2e-3, \
            jax.tree_util.keystr(path)
