"""The hybrid state-space / attention model (ISSUE 40) at a small size
with seeded weights on the CPU (the differential flash path in
interpret mode: tests/test_flash_diff.py): the join of the two
softmaxes, the ops round the scan, and
the whole small model (six kinds of layer, two of which read what an
earlier layer's recompute region made) against the benchmark's float32
reference (``chipbench/reference/sambay_lm.py``) on logits, loss and
every parameter's gradient; the eight shares of the vocabulary side by
side.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import small_model_test
from paddle_tpu.ops import diff_attention as DA
from paddle_tpu.ops import selective_scan as SS

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import cells  # noqa: E402
from chipbench.reference import sambay_lm  # noqa: E402


def _r(*shape, seed=0, scale=0.5):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


# -- the join of the two softmaxes ---------------------------------------------

def test_the_join_is_the_equations():
    """``RMSNorm(a1 - lam a2) * (1 - lam0)`` with every learned part
    drawn, against the same written out a head at a time."""
    a1, a2 = _r(2, 5, 3 * 16, seed=1), _r(2, 5, 3 * 16, seed=7)
    lq1, lk1, lq2, lk2 = (_r(8, seed=s, scale=0.3) for s in (2, 3, 4, 5))
    w = 1 + _r(16, seed=6, scale=0.2)
    got = DA.diff_combine(a1, a2, lq1, lk1, lq2, lk2, w, 0.35, 1e-5)
    lam = jnp.exp(lq1 @ lk1) - jnp.exp(lq2 @ lk2) + 0.35
    o = (a1 - lam * a2).reshape(2, 5, 3, 16)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5) * w * 0.65
    np.testing.assert_allclose(got, o.reshape(2, 5, 48), atol=1e-6)


# -- the convolution in front of the scan --------------------------------------

def test_the_convolution_is_causal_from_the_first_row():
    """Width 4, a channel by itself, zeros before the sequence: the
    first three rows see one, two and three inputs; a row never sees a
    later one."""
    x, w, b = _r(2, 9, 6, seed=1), _r(4, 6, seed=2), _r(6, seed=3)
    got = SS.causal_conv_silu(x, w, b)
    want = np.zeros((2, 9, 6), np.float32)
    for t in range(9):
        acc = np.asarray(b).copy()[None].repeat(2, 0)
        for i in range(4):
            if t - 3 + i >= 0:
                acc = acc + np.asarray(w[i]) * np.asarray(x[:, t - 3 + i])
        want[:, t] = acc / (1 + np.exp(-acc))
    np.testing.assert_allclose(got, want, atol=1e-6)
    later = x.at[:, 5:].set(7.0)
    np.testing.assert_array_equal(SS.causal_conv_silu(later, w, b)[:, :5],
                                  got[:, :5])


# -- the whole small model against the benchmark's reference -------------------

CFG = {"arch": "sambay", "vocab_size": 96, "num_hidden_layers": 6,
       "hidden_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "sliding_window": 12,
       "intermediate_size": 40, "layer_norm_eps": 1e-5,
       "layer_kinds": ["mamba", "sliding", "mamba_memory", "full", "gmu",
                       "cross"],
       "mamba": {"expand": 2, "d_state": 16, "d_conv": 4, "dt_rank": 2},
       "embedding_init_std": 0.02, "scan_chunk": 16,
       "scan_force": "interpret"}
SEQ = 40


def _small_model(cfg=CFG):
    return small_model_test.build("sambay", cfg, SEQ)


def _drawn(arch, scope, cfg=CFG):
    """Every parameter that initialises to a constant (biases 0, norms
    1, D 1, A_log the same in every channel) drawn instead, so that a
    dropped one shows."""
    rng = np.random.RandomState(5)
    names = jax.tree.leaves(arch.parameter_names(cfg))
    for name in names:
        was = np.asarray(scope.find_var(name))
        if was.ndim == 1 or name.endswith("a_log"):
            scope.set(name, jnp.asarray(
                was + rng.randn(*was.shape).astype(np.float32) * 0.3))
    return names


@pytest.fixture(scope="module")
def _initialised():
    return small_model_test.initialised("sambay", CFG, SEQ, _drawn)


@pytest.fixture
def small_model(_initialised):
    """(arch, main, forward, scope, cost, logits, the drawn names) as
    initialised, ONCE a file (tests/small_model_test.py)."""
    return small_model_test.as_initialised(*_initialised)


def _batch(rows=2, vocab=96):
    rng = np.random.RandomState(12)
    src = rng.randint(0, vocab, (rows, SEQ)).astype(np.int64)
    return {"src": src, "label": np.roll(src, -1, axis=1),
            "mask": (rng.rand(rows, SEQ) > 0.2).astype(np.float32)}


def test_small_model_loss_and_logits_are_the_references(small_model):
    """The for_test clone's loss and logits; the stack's kinds as the
    program's ops state them; the scans ran as the chunked kernels."""
    arch, main, forward, scope, cost, logits, names = small_model
    feed = _batch()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        params = arch.params_of_program(main, scope, CFG)
        got_cost, got_logits = exe.run(forward, feed=feed,
                                       fetch_list=[cost, logits])
        counters = arch.program_counters(main, scope)
    # 6 layers: 4 LayerNorm + MLP parameters each beside 12 of a Mamba,
    # 13 of attention, 2 of a GMU, 9 of cross; embedding, final norm
    assert len(names) == 6 * 7 + 2 * 12 + 2 * 13 + 2 + 9 + 3
    ops = [op for block in [main.global_block()] + [
        op.attr("sub_block") for op in main.global_block().ops
        if op.type == "recompute_block"] for op in block.ops]
    attention = [op for op in ops if op.type == "diff_attention"]
    assert [(op.attr("kind"), op.attr("window")) for op in attention] \
        == [("window", 12), ("full", 0), ("cross", 0)]
    # the cross layer reads the FULL layer's keys and values
    assert attention[2].input("K") == attention[1].input("K")
    assert attention[2].input("V") == attention[1].input("V")
    assert sum(op.type == "diff_attn" for op in ops) == 3      # the joins
    scans = [op for op in ops if op.type == "selective_scan"]
    (gmu,) = [op for op in ops if op.type == "gmu_gate"]
    assert len(scans) == 2 and gmu.input("X") == scans[1].output("Out")
    assert counters["scan_lowerings"].get("interpret/fwd", 0) >= 2
    assert "steps/fwd" not in counters["scan_lowerings"]
    # the reference as ONE program each: eagerly it is a hundred small
    # compilations, more seconds than the model under test takes
    want = jax.jit(lambda p, *batch: arch.lm_loss(p, *batch, CFG))(
        params, feed["src"], feed["label"], feed["mask"])
    np.testing.assert_allclose(got_cost, want, rtol=2e-5)
    logits_at = jax.jit(lambda p, tokens: arch.logits_at(p, tokens, 0, SEQ,
                                                         CFG))
    for row in range(2):
        ref = logits_at(params, jnp.asarray(feed["src"][row]))
        np.testing.assert_allclose(got_logits[row], ref, atol=3e-5)


def test_small_model_one_steps_gradients_are_the_references(small_model):
    """SGD at rate 1 turns a step's parameter change into its gradient:
    every parameter's against jax.grad of the reference's loss, through
    the recompute regions and through the two values that leave theirs
    (the memory, the full layer's k and v); the embedding's is the sum
    of its use as table and as head."""
    arch, main, _, scope, cost, _, _ = small_model
    feed = _batch()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        before = arch.params_of_program(main, scope, CFG)
        exe.run(main, feed=feed, fetch_list=[cost])
        after = arch.params_of_program(main, scope, CFG)
        counters = arch.program_counters(main, scope)
    assert counters["scan_lowerings"].get("interpret/bwd", 0) >= 2
    loss = lambda p: arch.lm_loss(p, feed["src"], feed["label"],
                                  feed["mask"], CFG)
    grads = jax.jit(jax.grad(loss))(before)
    moved = jax.tree.map(lambda a, b: a - b, before, after)
    flat_g, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), m in zip(flat_g, jax.tree.leaves(moved)):
        # (a key bias moves every score of a query alike: its gradient
        # is zero but for rounding, which the floor lets through)
        scale = float(np.max(np.abs(g))) + 1e-6
        assert float(np.max(np.abs(g - m))) / scale < 2e-3, \
            jax.tree_util.keystr(path)
    # the table's gradient is the sum of its two uses
    p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), before)

    def two_uses(table, head):
        x = jax.vmap(lambda tokens: sambay_lm._ln(sambay_lm.hidden(
            {**p32, "word_emb": table}, tokens, CFG), *p32["final_norm"],
            1e-5))(feed["src"])
        logp = jax.nn.log_softmax(x @ head.T)
        picked = jnp.take_along_axis(logp, feed["label"][..., None], -1)
        return -jnp.sum(picked[..., 0] * feed["mask"]) / feed["mask"].sum()

    as_table, as_head = jax.jit(jax.grad(two_uses, (0, 1)))(
        p32["word_emb"], p32["word_emb"])
    assert float(jnp.max(jnp.abs(as_table))) > 1e-4
    assert float(jnp.max(jnp.abs(as_head))) > 1e-4
    np.testing.assert_allclose(moved["word_emb"], as_table + as_head,
                               atol=2e-3 * float(jnp.max(jnp.abs(
                                   as_table + as_head))))


def test_eight_shares_of_the_vocabulary_add_up_to_the_uncut_head():
    """The share ties to the model: a chip of the 8-way vocabulary-
    parallel group holds an eighth of the table's rows, 16 of 128 here.
    The sequence's ids lie in share 3, so that share's program embeds
    them as the group's summed lookup would. Its logits are the uncut
    reference's columns 48..63; the stream it hands its head, times
    each other share's rows, gives that share's columns: side by side,
    the uncut reference's logits over the whole vocabulary."""
    arch = cells.load_arch("sambay")
    share = {**CFG, "vocab_size": 16}
    rng = np.random.RandomState(4)
    table = rng.randn(8 * 16, 32).astype(np.float32) * 0.02
    tokens = rng.randint(0, 16, (1, SEQ)).astype(np.int64)
    _, main, startup, forward, scope, _, logits = _small_model(share)
    (head,) = [op for op in forward.global_block().ops
               if op.type == "mul" and op.attr("transpose_Y", False)]
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        _drawn(arch, scope, share)
        scope.set("sambay_word_emb", jnp.asarray(table[48:64]))
        params = arch.params_of_program(main, scope, share)
        own, stream = exe.run(
            forward, feed={"src": tokens, "label": tokens,
                           "mask": np.ones((1, SEQ), np.float32)},
            fetch_list=[logits, head.input("X")[0]])
    uncut = jax.jit(lambda p, tokens: arch.logits_at(
        p, tokens, 0, SEQ, {**CFG, "vocab_size": 8 * 16}))(
            {**params, "word_emb": table}, jnp.asarray(tokens[0] + 48))
    side_by_side = [own[0] if chip == 3
                    else stream[0] @ table[chip * 16:(chip + 1) * 16].T
                    for chip in range(8)]
    np.testing.assert_allclose(np.concatenate(side_by_side, -1), uncut,
                               atol=3e-5)
