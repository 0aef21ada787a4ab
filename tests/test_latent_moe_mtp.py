"""``models/latent_moe.py`` on its plain stream and with its
multi-token-prediction module (ISSUE 55), at a small size with seeded
weights on the CPU, against the benchmark's float32 reference
(``chipbench/reference/joyai_lm.py``, which rotates the PUBLISHED
interleaved pairs): loss, main logits and module logits of the
``for_test`` clone; every parameter's gradient, the embedding table's
and the head's being the sums of their two uses', with and without the
recompute regions and with AMP on and off; the two loss sums a train
step adds to; and the 16 shares of a routed layer adding up to the
uncut layer.
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
from chipbench.reference import joyai_lm  # noqa: E402

CFG = {"arch": "joyai", "vocab_size": 96, "num_hidden_layers": 3,
       "first_k_dense_replace": 1, "hidden_size": 32,
       "num_attention_heads": 2, "q_lora_rank": 24, "kv_lora_rank": 16,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "intermediate_size": 40, "moe_intermediate_size": 24,
       "n_routed_experts": 4, "published": {"n_routed_experts": 8},
       "first_expert": 2, "num_experts_per_tok": 2, "n_shared_experts": 1,
       "norm_topk_prob": True, "routed_scaling_factor": 2.5,
       "bias_update_rate": 1e-3, "rope_theta": 32000000,
       "rope_interleave": True, "rope_scaling": None,
       "rms_norm_eps": 1e-6, "embedding_init_std": 1.0,
       "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3}
SEQ, V = 32, 96
NORMS = ("ln1", "ln2", "q_norm", "kv_norm", "final_norm", "enorm", "hnorm",
         "shared_head_norm")


def _norms_off_one(arch, scope):
    """Norm weights of 1 hide a norm that is left out or read in the
    wrong place: draw them round 1, the selection biases round 0."""
    rng = np.random.RandomState(5)
    for name in scope.local_var_names():
        if name.endswith(NORMS):
            shape = np.asarray(scope.find_var(name)).shape
            scope.set(name, jnp.asarray(
                1.0 + 0.3 * rng.randn(*shape), jnp.float32))
        elif name.endswith("_moe.bias"):
            scope.set(name, jnp.asarray(0.05 * rng.randn(8), jnp.float32))


def _batch(rows=2):
    rng = np.random.RandomState(12)
    src = rng.randint(3, V, (rows, SEQ)).astype(np.int64)
    label = np.roll(src, -1, axis=1)
    label[:, -1] = 0
    return {"src": src, "label": label,
            "mask": (rng.rand(rows, SEQ) > 0.2).astype(np.float32)}


@pytest.fixture(scope="module")
def _initialised():
    return small_model_test.initialised("joyai", CFG, SEQ, _norms_off_one)


@pytest.fixture
def small_model(_initialised):
    return small_model_test.as_initialised(*_initialised)[:6]


def test_the_program_is_plain_and_carries_one_module(small_model):
    arch, main, forward, _, _, logits = small_model
    ops = arch._ops(main)
    types = [op.type for op in ops]
    assert "hyper_connection" not in types
    # three layers' regions and the module's block's
    assert sum(op.type == "recompute_block"
               for op in main.global_block().ops) == 4
    assert types.count("mla_attention") == 4
    assert types.count("routed_experts") == 3
    assert tuple(logits.shape)[-1] == 2 * V
    module = [op for op in ops if op.attr("module") == "mtp"]
    assert {op.attr("module") for op in ops} == {None, "mtp"}
    assert [op.type for op in module].count("mla_attention") == 1
    # one table and one head, each read twice, once inside the module
    for kind, slot, name in (("lookup_table", "W", "joyai_word_emb"),
                             ("mul", "Y", "joyai_head")):
        uses = [op.attr("module") for op in ops
                if op.type == kind and op.input(slot) == [name]]
        assert uses == [None, "mtp"], (kind, uses)
    names = {p.name for p in main.global_block().all_parameters()}
    assert {"joyai_mtp_enorm", "joyai_mtp_hnorm", "joyai_mtp_eh_proj",
            "joyai_mtp_shared_head_norm", "joyai_mtp_moe.router"} <= names
    assert not [n for n in names if "_hc_" in n]


def test_a_module_on_lanes_or_a_second_module_is_refused():
    from paddle_tpu.models.latent_moe import latent_moe_lm
    sizes = dict(vocab_size=V, seq_len=SEQ, n_layer=1, n_dense=1,
                 d_model=32, n_head=2, q_rank=24, kv_rank=16, d_nope=16,
                 d_rope=8, d_v=16, d_dense=40, d_expert=24, num_experts=8,
                 experts_held=4)
    for asked in ({"n_nextn": 1, "hc_mult": 4},
                  {"n_nextn": 2, "hc_mult": None}):
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                pytest.raises(ValueError, match="one multi-token"):
            latent_moe_lm(**sizes, **asked)


def test_loss_and_both_logits_are_the_references(small_model):
    """The for_test clone's cost and logits, main and module side by
    side, with all three routers' choices fetched from inside their
    recompute regions in the same run; a for_test run adds nothing to
    the loss sums."""
    arch, main, forward, scope, cost, logits = small_model
    feed = _batch()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        params = arch.params_of_program(main, scope, CFG)
        names = arch.router_choices(forward)
        fetched = exe.run(forward, feed=feed,
                          fetch_list=[cost, logits] + list(names))
        counters = arch.program_counters(main, scope)
    got_cost, got_logits, choices = fetched[0], fetched[1], fetched[2:]
    want = jax.jit(lambda p, *batch: arch.lm_loss(p, *batch, CFG))(
        params, feed["src"], feed["label"], feed["mask"])
    np.testing.assert_allclose(got_cost, want, rtol=2e-5)
    assert len(choices) == 3 and choices[0].shape == (2, SEQ, 2)
    assert counters["steps"] == [0] and sum(counters["expert_rows"]) == 0
    assert counters["main_loss"] == [0.0] and counters["mtp_loss"] == [0.0]
    logits_at = jax.jit(lambda p, tokens, chosen=None: arch.logits_at(
        p, tokens, 0, SEQ, CFG, chosen))
    for row in range(2):
        ref = np.asarray(logits_at(params, jnp.asarray(feed["src"][row])))
        assert ref.shape == (SEQ, 2 * V)
        for part, at in (("main", slice(0, V)), ("module", slice(V, 2 * V))):
            np.testing.assert_allclose(got_logits[row][:, at], ref[:, at],
                                       atol=3e-5, err_msg=part)
        # the two heads differ: the module is no copy of the main model
        assert np.abs(ref[:, :V] - ref[:, V:]).max() > 0.1
        handed = logits_at(params, jnp.asarray(feed["src"][row]),
                           np.stack([c[row:row + 1] for c in choices]))
        np.testing.assert_allclose(handed, ref, atol=1e-6)


def test_the_reference_turns_published_pairs():
    """The reference's rotary embedding is the interleaved one:
    columns (2i, 2i + 1) turn together at theta^(-2i/Dr), as
    ``apply_rotary_pos_emb_interleave`` de-interleaves, rotates halves
    and leaves the score unchanged."""
    cfg = {"qk_rope_head_dim": 8, "rope_theta": 32000000}
    x = jnp.asarray(np.random.RandomState(3).randn(5, 2, 8), jnp.float32)
    freqs = joyai_lm.frequencies(cfg)
    np.testing.assert_allclose(freqs, 32000000.0 ** (-np.arange(4) / 4.0),
                               rtol=1e-6)
    got = joyai_lm._rope(x, freqs)
    ang = np.arange(5)[:, None] * np.asarray(freqs)          # [T, 4]
    for i in range(4):
        c, s = np.cos(ang[:, i])[:, None], np.sin(ang[:, i])[:, None]
        np.testing.assert_allclose(
            got[..., 2 * i], x[..., 2 * i] * c - x[..., 2 * i + 1] * s,
            atol=1e-5)
        np.testing.assert_allclose(
            got[..., 2 * i + 1], x[..., 2 * i + 1] * c + x[..., 2 * i] * s,
            atol=1e-5)
    np.testing.assert_allclose(got[0], x[0], atol=1e-7)       # position 0


# -- gradients ------------------------------------------------------------------

def _floats(p):
    strip = lambda layer: {k: v for k, v in layer.items() if k != "bias"}
    return {**p, "layers": [strip(layer) for layer in p["layers"]],
            "mtp": strip(p["mtp"])}


@pytest.fixture(scope="module")
def reference_gradients(_initialised):
    """(parameters before any step, jax.grad of the reference's cost,
    jax.grad of its main term alone): ONE compilation each for the four
    cases below."""
    model = small_model_test.as_initialised(*_initialised)
    arch, main, _, scope = model[:4]
    feed = _batch()
    with fluid.scope_guard(scope):
        before = arch.params_of_program(main, scope, CFG)
    biases = [layer.get("bias") for layer in before["layers"]]

    def whole(p):
        return {**p, "layers": [
            layer if bias is None else {**layer, "bias": bias}
            for layer, bias in zip(p["layers"], biases)],
            "mtp": {**p["mtp"], "bias": before["mtp"]["bias"]}}

    batch = (feed["src"], feed["label"], feed["mask"])
    cost = lambda p: arch.lm_loss(whole(p), *batch, CFG)
    main_alone = lambda p: joyai_lm.loss_terms(whole(p), *batch, CFG)[0]
    return (before, jax.jit(jax.grad(cost))(_floats(before)),
            jax.jit(jax.grad(main_alone))(_floats(before)))


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "amp"])
@pytest.mark.parametrize("regions", [True, False],
                         ids=["regions", "no_regions"])
def test_one_steps_gradients_are_the_references(
        monkeypatch, _initialised, reference_gradients, regions, amp):
    """SGD at rate 1 turns a step's parameter change into its gradient:
    every parameter's against jax.grad of the reference's ``L_main +
    0.3 L_mtp``. The table and the head are each ONE parameter read
    twice, and their change is the sum of both uses' gradients, which
    the main term's alone is far from. Under AMP the head is cast for
    each use and the two bf16 gradients meet in float32."""
    before, grads, main_alone = reference_gradients
    if regions:
        arch, main, _, scope, cost, _ = small_model_test.as_initialised(
            *_initialised)[:6]
    else:
        from paddle_tpu.models import latent_moe as model
        whole = model.latent_moe_lm
        monkeypatch.setattr(model, "latent_moe_lm", lambda **kw: whole(
            **{**kw, "recompute": False}))
        (arch, main, _, scope, cost, _), _ = small_model_test.initialised(
            "joyai", CFG, SEQ)
        for name, value in _initialised[1].items():   # the same weights
            scope.set(name, jnp.asarray(value))
        assert not [op for op in main.global_block().ops
                    if op.type == "recompute_block"]
    feed = _batch()
    fluid.amp.enable_amp(amp)
    try:
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            terms = exe.run(main, feed=feed, fetch_list=[cost])
            after = arch.params_of_program(main, scope, CFG)
            counters = arch.program_counters(main, scope)
    finally:
        fluid.amp.enable_amp(False)
    # the regions run twice a step and the sums move ONCE
    assert counters["steps"] == [1]
    assert sum(counters["expert_rows"]) == 3 * 2 * SEQ * 2
    want = joyai_lm.loss_terms(before, feed["src"], feed["label"],
                               feed["mask"], CFG)
    tol = 2e-2 if amp else 2e-5
    assert counters["main_loss"][0] == pytest.approx(float(want[0]), rel=tol)
    assert counters["mtp_loss"][0] == pytest.approx(float(want[1]), rel=tol)
    assert float(terms[0]) == pytest.approx(
        float(want[0] + 0.3 * want[1]), rel=tol)
    moved = jax.tree.map(lambda a, b: a - b, _floats(before), _floats(after))
    flat_g, _ = jax.tree_util.tree_flatten_with_path(grads)
    # embedding, final norm, head; four blocks: 12 of attention and norms,
    # 3 of the dense FFN or 3 + 4 of experts; the module's four more
    assert len(flat_g) == 3 + 4 * 12 + 3 + 3 * 7 + 4
    # float32: rounding. AMP: bf16 products; a router's gradient comes
    # through its experts' bf16 outputs (0.10 read), the others 0.04
    limit = 0.15 if amp else 2e-3
    for (path, g), m in zip(flat_g, jax.tree.leaves(moved)):
        scale = float(np.max(np.abs(g))) + 1e-8
        assert float(np.max(np.abs(g - m))) / scale < limit, \
            jax.tree_util.keystr(path)
    for shared in ("word_emb", "w_out"):
        # one use's gradient alone is 0.49 and 0.32 of the largest away
        scale = float(np.max(np.abs(grads[shared])))
        apart = float(np.max(np.abs(grads[shared] - main_alone[shared])))
        assert apart / scale > 2 * limit, shared


def test_a_target_one_place_short_is_seen_by_the_heads_gradient(
        monkeypatch, _initialised, reference_gradients):
    """The module's loss against ``x_{t+1}`` (the fed label) where it
    should be ``x_{t+2}``: a fresh model's loss moves by noise alone
    (both targets cost about ln V), so the benchmark's ``correct`` sees
    it only where the noise is over ``LOSS_RTOL`` (``tests/chipbench/
    test_chipbench_joyai.py``). The head's gradient is what the target
    decides: it parts from the reference's wholesale."""
    from paddle_tpu.models import latent_moe as model
    before, grads, _ = reference_gradients
    sound, labels = model.lm_cost, []

    def cost_against_the_first_label(logits, label, mask, vocab):
        labels.append(label)
        return sound(logits, labels[0], mask, vocab)
    monkeypatch.setattr(model, "lm_cost", cost_against_the_first_label)
    (arch, main, _, scope, cost, _), _ = small_model_test.initialised(
        "joyai", CFG, SEQ)
    for name, value in _initialised[1].items():       # the same weights
        scope.set(name, jnp.asarray(value))
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(main, feed=_batch(),
                                             fetch_list=[cost])
        after = arch.params_of_program(main, scope, CFG)
    moved = before["w_out"] - after["w_out"]
    scale = float(np.max(np.abs(grads["w_out"])))
    assert float(np.max(np.abs(grads["w_out"] - moved))) / scale > 0.1


# -- the share tied to the model --------------------------------------------------

def test_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """16 chips holding 2 of 32 experts each, top-8: the routed outputs
    of the PROGRAM's expert layer over the sixteen shares, with the
    shared expert, which every chip holds whole, counted once, add up
    to the REFERENCE's layer that holds all 32."""
    e, k, d, f, n = 32, 8, 16, 12, 24
    rng = np.random.RandomState(60)
    r = lambda *shape, scale=0.5: jnp.asarray(rng.randn(*shape) * scale,
                                              jnp.float32)
    x, router = r(n, d, scale=1.0), r(d, e)
    wg, wu, wd = r(e, d, f), r(e, d, f), r(e, f, d)
    shared = (r(d, f), r(d, f), r(f, d))
    bias = r(e, scale=0.05)
    cfg = {"num_attention_heads": 1, "rms_norm_eps": 1e-6,
           "qk_nope_head_dim": 4, "qk_rope_head_dim": 4, "v_head_dim": 4,
           "rope_theta": 1e4, "num_experts_per_tok": k,
           "published": {"n_routed_experts": e}, "first_expert": 0,
           "n_routed_experts": e, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5}
    layer = {"ln2": jnp.ones(d), "router": router, "bias": bias,
             "shared": shared, "w_gate": wg, "w_up": wu, "w_down": wd}
    h = joyai_lm._rms(x, layer["ln2"], 1e-6)
    whole = joyai_lm.expert_layer(layer, x, cfg)
    routed = lambda first, held: moe.routed_experts(
        h, router, wg[first:first + held], wu[first:first + held],
        wd[first:first + held], e, first, k, True, score="sigmoid",
        scaling=2.5, shared_expert=True, bias=bias)[0]
    once = (jax.nn.silu(h @ shared[0]) * (h @ shared[1])) @ shared[2]
    shares = sum(routed(first, 2) for first in range(0, e, 2))
    np.testing.assert_allclose(once + shares, whole, atol=3e-5)
    # once, not never and not sixteen times
    assert float(jnp.max(jnp.abs(whole - shares))) > 1e-2
    assert float(jnp.max(jnp.abs(whole - (16 * once + shares)))) > 1e-1
    # and one share alone is the reference's layer cut to that share
    cut = joyai_lm.expert_layer(
        {**layer, "w_gate": wg[6:8], "w_up": wu[6:8], "w_down": wd[6:8]}, x,
        {**cfg, "first_expert": 6, "n_routed_experts": 2})
    np.testing.assert_allclose(once + routed(6, 2), cut, atol=3e-5)
