"""The hybrid state-space / attention model (ISSUE 40) at a small size
with seeded weights on the CPU: the differential flash path in
interpret mode against dense float32 math, the ops round the scan, and
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
from paddle_tpu.ops import diff_attention as DA
from paddle_tpu.ops import flash_attention as FA
from paddle_tpu.ops import selective_scan as SS

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import cells  # noqa: E402
from chipbench.reference import sambay_lm  # noqa: E402


def _r(*shape, seed=0, scale=0.5):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


# -- differential attention through the flash kernels --------------------------

def _two_softmaxes(q, k, v, h, hkv, window):
    """The equations, dense float32: (a1, a2), [B, T, (H/2)*2D]
    each."""
    (b, t, hd), d = q.shape, q.shape[-1] // h
    f32 = lambda x: x.astype(jnp.float32)
    qh, kh, vh = (f32(x).reshape(b, t, -1, d) for x in (q, k, v))
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = ahead >= 0 if not window else (ahead >= 0) & (ahead < window)
    out = [[], []]
    for p in range(h // 2):
        r = p // (h // hkv)
        value = jnp.concatenate([vh[:, :, 2 * r], vh[:, :, 2 * r + 1]], -1)
        for turn in range(2):
            s = jnp.einsum("bqd,bkd->bqk", qh[:, :, 2 * p + turn],
                           kh[:, :, 2 * r + turn]) * d ** -0.5
            out[turn].append(jnp.einsum(
                "bqk,bkd->bqd",
                jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), value))
    return tuple(jnp.concatenate(a, -1) for a in out)


# T 512 in streamed blocks of 128: a window under a block, of one block,
# no multiple of a block; none; all of T in one block
_DIFF = [(512, 128, 100, "window_under_a_block"),
         (512, 128, 128, "window_of_a_block"),
         (512, 128, 200, "window_no_multiple_of_a_block"),
         (512, 128, 0, "full"), (256, None, 0, "full_one_block"),
         (256, None, 72, "window_one_block")]


@pytest.mark.parametrize("t, block, window", [c[:3] for c in _DIFF],
                         ids=[c[3] for c in _DIFF])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_differential_flash_matches_the_two_softmaxes(dtype, t, block,
                                                      window):
    """8 query and 4 key/value heads of 64, values of 128, in interpret
    mode against the equations in dense float32: out, dq, dk, dv; the
    dense form (the CPU's path) beside them. No lowering is dense and
    the kernels are the streamed set's own."""
    h, hkv, d = 8, 4, 64
    mk = lambda n, s: _r(1, t, n * d, seed=s).astype(dtype)
    q, k, v = mk(h, t + window), mk(hkv, 1), mk(hkv, 2)
    dy = _r(2, 1, t, h * d, seed=3).astype(dtype)
    kw = dict(window=window or None, block_q=block, block_k=block)
    both = lambda fn: lambda *a: jnp.stack(fn(*a))
    run = both(lambda q, k, v: FA.flash_diff_bthd(
        q, k, v, h, hkv, force="interpret", **kw))
    dense = both(lambda q, k, v: FA.flash_diff_bthd(
        q, k, v, h, hkv, force="dense", **kw))
    want = both(lambda q, k, v: _two_softmaxes(q, k, v, h, hkv, window))
    f32 = lambda x: x.astype(jnp.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    close = lambda name, a, b: np.testing.assert_allclose(
        f32(a), f32(b), atol=tol * max(float(jnp.max(jnp.abs(f32(b)))), 0.1),
        err_msg=name)
    o = run(q, k, v)
    assert o.shape == (2, 1, t, h * d) and o.dtype == dtype
    close("out", o, want(q, k, v))
    close("dense out", dense(q, k, v), want(q, k, v))
    loss = lambda fn: lambda *a: (f32(fn(*a)) * f32(dy)).sum()
    grad = jax.grad(loss(run), (0, 1, 2))
    names = [eqn.params["name"] for eqn in _pallas_eqns(
        jax.make_jaxpr(grad)(q, k, v).jaxpr)]
    assert sorted(names) == ["flash_bwd"] * 2 + ["flash_fwd"] * 2
    truth = jax.grad(loss(want), (0, 1, 2))(f32(q), f32(k), f32(v))
    for name, a, b, c in zip(("dq", "dk", "dv"), grad(q, k, v), truth,
                             jax.grad(loss(dense), (0, 1, 2))(q, k, v)):
        assert a.shape == b.shape and a.dtype == dtype
        close(name, a, b)
        close("dense " + name, c, b)


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


def test_a_differential_call_counts_itself_as_grouped_heads_of_128():
    """The lowering's labels: the entry ``diff``, one head to a block,
    the pairs' groups and the widths as laid out, the window; odd head
    counts raise."""
    count = lambda **want: sum(
        v for key, v in FA._LOWERINGS.snapshot().items()
        if all(key[FA._LOWERINGS.label_names.index(k)] == x
               for k, x in want.items()))
    labels = dict(entry="diff", path="interpret", heads_per_block="1",
                  kv_groups="4", key_width="128", value_width="128",
                  window="100", mask="causal")
    before = count(**labels)
    q = jnp.zeros((1, 256, 8 * 64), jnp.float32)
    FA.flash_diff_bthd(q, q[..., :128], q[..., :128], 8, 2, window=100,
                       force="interpret", block_q=128, block_k=128)
    assert count(**labels) == before + 2                # one a softmax
    with pytest.raises(ValueError, match="pairs its heads"):
        FA.flash_diff_bthd(q, q[..., :192], q[..., :192], 8, 3)


def test_diff_heads_zeroes_the_other_heads_lanes():
    q = jnp.arange(2 * 4 * 3, dtype=jnp.float32).reshape(1, 2, 12) + 1
    even, odd = (FA.diff_heads(q, 4, turn) for turn in (0, 1))
    np.testing.assert_array_equal(
        even[0, 0], [1, 2, 3, 0, 0, 0, 7, 8, 9, 0, 0, 0])
    np.testing.assert_array_equal(
        odd[0, 1], [0, 0, 0, 16, 17, 18, 0, 0, 0, 22, 23, 24])
    np.testing.assert_array_equal(even + odd, q)


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
    arch = cells.load_arch("sambay")
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        cost, logits = arch.build(cfg, SEQ)
        forward = main.clone(for_test=True)
    return arch, main, startup, forward, scope, cost, logits


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


def _batch(rows=2, vocab=96):
    rng = np.random.RandomState(12)
    src = rng.randint(0, vocab, (rows, SEQ)).astype(np.int64)
    return {"src": src, "label": np.roll(src, -1, axis=1),
            "mask": (rng.rand(rows, SEQ) > 0.2).astype(np.float32)}


def test_small_model_loss_and_logits_are_the_references():
    """The for_test clone's loss and logits; the stack's kinds as the
    program's ops state them; the scans ran as the chunked kernels."""
    arch, main, startup, forward, scope, cost, logits = _small_model()
    feed = _batch()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        names = _drawn(arch, scope)
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
    want = arch.lm_loss(params, feed["src"], feed["label"], feed["mask"], CFG)
    np.testing.assert_allclose(got_cost, want, rtol=2e-5)
    for row in range(2):
        ref = arch.logits_at(params, jnp.asarray(feed["src"][row]), 0, SEQ,
                             CFG)
        np.testing.assert_allclose(got_logits[row], ref, atol=3e-5)


def test_small_model_one_steps_gradients_are_the_references():
    """SGD at rate 1 turns a step's parameter change into its gradient:
    every parameter's against jax.grad of the reference's loss, through
    the recompute regions and through the two values that leave theirs
    (the memory, the full layer's k and v); the embedding's is the sum
    of its use as table and as head."""
    arch, main, startup, _, scope, cost, _ = _small_model()
    feed = _batch()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        fluid.optimizer.SGD(learning_rate=1.0).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        _drawn(arch, scope)
        before = arch.params_of_program(main, scope, CFG)
        exe.run(main, feed=feed, fetch_list=[cost])
        after = arch.params_of_program(main, scope, CFG)
        counters = arch.program_counters(main, scope)
    assert counters["scan_lowerings"].get("interpret/bwd", 0) >= 2
    loss = lambda p: arch.lm_loss(p, feed["src"], feed["label"],
                                  feed["mask"], CFG)
    grads = jax.grad(loss)(before)
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

    as_table, as_head = jax.grad(two_uses, (0, 1))(p32["word_emb"],
                                                   p32["word_emb"])
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
    uncut = arch.logits_at({**params, "word_emb": table},
                           jnp.asarray(tokens[0] + 48), 0, SEQ,
                           {**CFG, "vocab_size": 8 * 16})
    side_by_side = [own[0] if chip == 3
                    else stream[0] @ table[chip * 16:(chip + 1) * 16].T
                    for chip in range(8)]
    np.testing.assert_allclose(np.concatenate(side_by_side, -1), uncut,
                               atol=3e-5)
