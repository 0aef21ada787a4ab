"""``models/looped_lm.py`` and ``layers.repeat`` (ISSUE 59) on the CPU
at a small size, seeded random weights, float32 (AMP off): the looped
program against the plain reference ``chipbench/reference/ouro_lm.py``
in its loss, its last visit's logits, the exit distribution and the
GRADIENTS (a shared parameter's is the sum over the visits'), and
``repeat`` against the same block written out `times` times."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

import paddle_tpu as fluid                                  # noqa: E402
from paddle_tpu import layers                               # noqa: E402
from paddle_tpu.models import looped_lm as model            # noqa: E402
from paddle_tpu.models.latent_moe import _linear, _norm     # noqa: E402
from paddle_tpu.models.transformer import lm_cost           # noqa: E402
from chipbench import cells                                 # noqa: E402
from chipbench.reference import ouro_lm                     # noqa: E402

CFG = dict(vocab_size=64, num_hidden_layers=2, hidden_size=32,
           num_attention_heads=2, num_key_value_heads=2, head_dim=16,
           intermediate_size=48, total_ut_steps=4, rope_theta=1e6,
           rms_norm_eps=1e-6, entropy_weight=0.1)
B, T = 2, 16
ARCH = cells.load_arch("ouro")


def _feeds(seed=0):
    rng = np.random.RandomState(seed)
    return {"src": rng.randint(0, 64, (B, T)).astype(np.int64),
            "label": rng.randint(0, 64, (B, T)).astype(np.int64),
            "mask": (rng.rand(B, T) > 0.2).astype(np.float32)}


def _looped(cfg=CFG, recompute=True, seed=7):
    """(main, its for_test clone, cost, logits, scope, executor) of the
    looped program with its start-up run, the gate drawn off zero so
    that the exit distribution is no constant."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        cost, logits = model.looped_lm(
            cfg["vocab_size"], T, cfg["num_hidden_layers"],
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["total_ut_steps"],
            cfg["rope_theta"], cfg["rms_norm_eps"], cfg["entropy_weight"],
            recompute, ARCH.NAME)
        forward = main.clone(for_test=True)
        fluid.backward.append_backward(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    rng = np.random.RandomState(1)
    scope.set("ouro_gate_w", jnp.asarray(
        rng.randn(cfg["hidden_size"], 1).astype(np.float32) * 0.3))
    scope.set("ouro_gate_b", jnp.asarray([0.2], jnp.float32))
    return main, forward, cost, logits, scope, exe


def _params(main, scope, cfg=CFG):
    return jax.tree.map(np.asarray, ARCH.params_of_program(main, scope, cfg))


def _run(exe, scope, program, feed, fetch):
    with fluid.scope_guard(scope):
        return exe.run(program, feed=feed, fetch_list=fetch)


@pytest.mark.parametrize("recompute", [False, True])
def test_the_program_is_the_reference(recompute):
    """Loss, the last visit's logits and the four ``log p_t``, and the
    counters' terms, to float32's rounding."""
    main, forward, cost, logits, scope, exe = _looped(recompute=recompute)
    params, feed = _params(main, scope), _feeds()
    got_cost, got = _run(exe, scope, main, feed, [cost, logits])
    want = ouro_lm.lm_loss(params, feed["src"], feed["label"],
                           feed["mask"], CFG)
    assert float(got_cost) == pytest.approx(float(want), rel=2e-6)
    for b in range(B):
        ref = np.asarray(ouro_lm.logits_at(
            params, jnp.asarray(feed["src"][b]), 0, T, CFG))
        assert ref.shape == (T, 64 + 4)
        np.testing.assert_allclose(got[b], ref, atol=2e-6, rtol=2e-6)
    # the exit distribution sums to 1 in every row, and is no constant
    p = np.exp(got[..., -4:])
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    assert p.std(axis=(0, 1)).min() > 1e-3
    # under clone(for_test=True) the same logits, and no counter moves
    (again,) = _run(exe, scope, forward, feed, [logits])
    np.testing.assert_allclose(again, got, atol=2e-6, rtol=2e-6)
    counters = ARCH.program_counters(main, scope)
    visit, p_mean, entropy = ouro_lm.visit_losses(
        params, feed["src"], feed["label"], feed["mask"], CFG)
    assert counters["steps"] == [pytest.approx(1.0)]
    np.testing.assert_allclose(counters["visit_loss"], visit, rtol=1e-5)
    assert counters["exit_step"][0] == pytest.approx(
        float(jnp.sum(p_mean * jnp.arange(1, 5))), rel=1e-5)
    assert counters["entropy"][0] == pytest.approx(float(entropy), rel=1e-5)
    assert 1.0 < counters["exit_step"][0] < 4.0


def test_a_shared_parameters_gradient_is_the_sum_over_the_visits():
    """The reference differentiated with R UNTIED copies of the stack,
    each holding the same values: the program's gradient of a stack
    parameter is the sum of its four copies' gradients; the head's, the
    final norm's and the gate's (read at every visit too) and the
    table's are the reference's own. The loss's gradient reaches the
    gate."""
    main, _, cost, _, scope, exe = _looped()
    params, feed = _params(main, scope), _feeds()
    untied = dict(params, visits=[params["layers"]] * 4)
    del untied["layers"]
    want = jax.grad(lambda p: ouro_lm.lm_loss(
        p, feed["src"], feed["label"], feed["mask"], CFG))(untied)
    names = {"ouro_word_emb": want["word_emb"], "ouro_head": want["w_out"],
             "ouro_final_norm": want["final_norm"],
             "ouro_gate_w": want["gate_w"], "ouro_gate_b": want["gate_b"]}
    for i in range(2):
        for key in ARCH.LAYER_KEYS + ("gate", "up", "down"):
            name = "ouro_l%d_%s%s" % (i, "ffn_" * (key in (
                "gate", "up", "down")), key)
            copies = [np.asarray(want["visits"][t][i][key])
                      for t in range(4)]
            # no visit's share is nothing: the sum is of four terms
            assert min(np.abs(c).max() for c in copies) > 0
            names[name] = sum(copies)
    got = _run(exe, scope, main, feed, [n + "@GRAD" for n in names])
    for (name, ref), g in zip(names.items(), got):
        ref = np.asarray(ref)
        np.testing.assert_allclose(g, ref, atol=2e-6 + 1e-5 * np.abs(
            ref).max(), err_msg=name)
    by_name = dict(zip(names, got))
    assert np.abs(by_name["ouro_gate_w"]).max() > 1e-4
    assert abs(float(by_name["ouro_gate_b"][0])) > 1e-4


def test_one_visit_and_no_entropy_weight_is_a_plain_lm():
    """`ut_steps` 1, `entropy_weight` 0: the cost, the logits and every
    gradient of a sandwich-norm LM built WITHOUT ``repeat`` from the
    same parts under the same parameter names; the one ``log p`` is 0
    and the gate's gradient nothing."""
    cfg = dict(CFG, total_ut_steps=1, entropy_weight=0.0)
    main, _, cost, logits, scope, exe = _looped(cfg)
    plain, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(plain, startup):
        src = layers.data("src", [T], dtype="int64")
        label = layers.data("label", [T], dtype="int64")
        mask = layers.data("mask", [T], dtype="float32")
        s = layers.embedding(src, size=[64, 32],
                             param_attr=fluid.ParamAttr(name="ouro_word_emb"))
        for i in range(2):
            s = model.sandwich_layer(s, "ouro_l%d" % i, 2, 2, 16, 48, 1e6,
                                     1e-6)
        plain_logits = _linear(_norm(s, "ouro_final_norm", 1e-6), 64,
                               "ouro_head")
        plain_cost = lm_cost(plain_logits, label, mask, 64)
        fluid.backward.append_backward(plain_cost)
    assert not [o for o in plain.global_block().ops if o.type == "repeat"]
    weights = [p.name for p in plain.global_block().all_parameters()]
    feed = _feeds(3)
    got = _run(exe, scope, main, feed, [cost, logits, "ouro_gate_w@GRAD"]
               + [n + "@GRAD" for n in weights])
    want = _run(exe, scope, plain, feed, [plain_cost, plain_logits]
                + [n + "@GRAD" for n in weights])
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    np.testing.assert_allclose(got[1][..., :64], want[1], atol=1e-6)
    assert np.all(got[1][..., 64:] == 0.0) and got[1].shape[-1] == 65
    assert np.all(got[2] == 0.0)
    for name, g, ref in zip(weights, got[3:], want[2:]):
        np.testing.assert_allclose(g, ref, atol=1e-6, err_msg=name)


def test_the_program_holds_the_stack_once():
    """One ``repeat`` op in the main block; the stack's ops, its
    regions and every parameter ONCE whatever the visits; each visit's
    head and loss in ``loop_head``, the gate, the distribution and the
    glue in ``exit``."""
    main, forward, *_ = _looped()
    for program in (main, forward):
        top = program.global_block().ops
        (loop,) = [o for o in top if o.type == "repeat"]
        assert loop.attr("times") == 4 and ARCH.program_visits(program) == 4
        body = loop.attr("sub_block").ops
        regions = [o for o in body if o.type == "recompute_block"]
        assert len(regions) == 2 + 1
        inner = [m for r in regions for m in r.attr("sub_block").ops]
        assert sum(m.type == "mul" for m in inner) == 2 * 7 + 1
        assert sum(m.type == "causal_attention" for m in inner) == 2
        # what the block reads of its parent is the op's real input
        assert {"label", "ouro_head", "ouro_l1_wq", "ouro_gate_w"} \
            <= set(loop.input("X"))
        assert [m.attr("module") for m in regions[-1].attr(
            "sub_block").ops] == ["loop_head"] * 5
        gate = [m for m in body if m.attr("module") == "exit"]
        assert [m.type for m in gate] == ["mul", "elementwise_add",
                                          "reshape"]
        assert all(m.attr("float32") for m in gate)
        after = top[top.index(loop) + 1:]
        assert after[0].type == "exit_distribution"
        assert {o.attr("module") for o in after
                if o.type not in ("backward_marker",)} == {"exit",
                                                           "loop_head"}
    names = [p.name for p in main.global_block().all_parameters()]
    assert len(names) == len(set(names)) == 2 * 11 + 5
    assert len(main.blocks) == 1 + 1 + 3


def test_what_is_built_forward_only_is_no_part_of_a_train_step():
    """The last visit's logits are built under ``layers.forward_only``:
    a train step that fetches the cost lowers four uses of the head, a
    visit each, and its op ledger has no row for a fifth; one that
    fetches the logits lowers them, as the ``for_test`` clone does."""
    from paddle_tpu import trace
    main, forward, cost, logits, scope, exe = _looped()
    held_out = [o for o in main.global_block().ops
                if o.attr("forward_only")]
    assert [o.type for o in held_out] == ["mul", "cast", "transpose",
                                          "concat"]
    assert not [o for o in main.global_block().ops[1].attr(
        "sub_block").ops if o.attr("forward_only")]
    heads = lambda: sum(r["type"] == "mul" and r["weights"] == ("ouro_head",)
                        for r in trace.ops(root=None, backward=True)[1])
    _run(exe, scope, main, _feeds(), [cost])
    assert heads() == 4
    _run(exe, scope, main, _feeds(), [cost, logits])
    assert heads() == 5


# -- ``repeat`` against its block written out ---------------------------------

def _fc(x, size, name):
    return layers.fc(x, size, bias_attr=False,
                     param_attr=fluid.ParamAttr(name=name))


def _toy(times, looped, recompute, seed=5):
    """``s <- tanh(s W) + s`` `times` times under ONE W, a per-visit
    output ``mean(s^2)``, the cost their sum plus the last s's mean:
    through ``layers.repeat`` or written out."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    region = layers.recompute if recompute else __import__(
        "contextlib").nullcontext

    def visit(s):
        with region():
            s = layers.elementwise_add(layers.tanh(_fc(s, 8, "w")), s)
        return s, layers.reduce_mean(layers.square(s), dim=1)

    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = layers.data("x", [8], dtype="float32")
        x.stop_gradient = False
        if looped:
            loop = layers.repeat(times)
            with loop.block():
                carried = loop.carry(x)
                s, out = visit(carried)
                loop.update(carried, s)
                loop.output(out)
            (outs,) = loop()
            s = loop.final(carried)
        else:
            s, each = x, []
            for _ in range(times):
                s, out = visit(s)
                each.append(layers.reshape(out, [1, -1]))
            outs = layers.concat(each, axis=0)
        cost = layers.elementwise_add(layers.reduce_sum(outs),
                                      layers.reduce_mean(s))
        forward = main.clone(for_test=True)
        fluid.backward.append_backward(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    feed = {"x": np.random.RandomState(2).randn(4, 8).astype(np.float32)}
    run = lambda program, fetch: _run(exe, scope, program, feed, fetch)
    return (run(main, [cost, outs, s, "w@GRAD"]), run(forward, [outs, s]),
            main)


@pytest.mark.parametrize("times", [1, 3])
@pytest.mark.parametrize("recompute", [False, True])
def test_repeat_is_its_block_written_out(times, recompute):
    """Values and the shared weight's gradient, with and without a
    recompute region inside, and under ``clone(for_test=True)``; the
    looped program holds ONE `mul`."""
    got, got_fwd, looped = _toy(times, True, recompute)
    want, want_fwd, written = _toy(times, False, recompute)
    for a, b in zip(got + got_fwd, want + want_fwd):
        np.testing.assert_allclose(np.asarray(a).reshape(-1),
                                   np.asarray(b).reshape(-1), atol=1e-6)
    assert got[1].shape == (times, 4)
    count = lambda p: sum(o.type == "mul" for b in p.blocks for o in b.ops)
    assert (count(looped), count(written)) == (1, times)


def test_repeat_says_what_it_was_not_given():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="at least once"):
            layers.repeat(0)
        x = layers.data("x", [8], dtype="float32")
        loop = layers.repeat(2)
        with pytest.raises(ValueError, match="never updated"):
            with loop.block():
                loop.carry(x)
        other = layers.repeat(2)
        with other.block():
            carried = other.carry(x)
            with pytest.raises(ValueError, match="no carried variable"):
                other.update(x, carried)
            other.update(carried, layers.tanh(carried))


# -- float32 under AMP ---------------------------------------------------------

def test_the_gate_is_float32_whatever_amp_says():
    """Under bf16 AMP a `mul` built under ``amp.float32`` reads float32
    operands and hands on float32 at the highest precision; the one
    beside it is AMP's. The exit distribution is float32 from bfloat16
    logits too, and sums to 1."""
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = layers.data("x", [8], dtype="float32")
        narrow = _fc(x, 4, "w")
        with fluid.amp.float32():
            wide = _fc(x, 4, "w")
            again = _fc(narrow, 4, "w2")       # a bf16 operand widened
        log_p = layers.exit_distribution(layers.transpose(narrow, [1, 0]))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"x": np.random.RandomState(0).randn(3, 8).astype(np.float32)}
        with fluid.amp.amp_guard(True):
            got = exe.run(main, feed=feed, return_numpy=False,
                          fetch_list=[narrow, wide, again, log_p])
    assert [str(g.dtype) for g in got] == ["bfloat16", "float32", "float32",
                                           "float32"]
    w = np.asarray(scope.find_var("w"))
    np.testing.assert_allclose(np.asarray(got[1]), feed["x"] @ w, atol=1e-6)
    assert np.abs(np.asarray(got[0], np.float32) - feed["x"] @ w).max() > 1e-4
    np.testing.assert_allclose(np.exp(np.asarray(got[3])).sum(0), 1.0,
                               atol=1e-6)
    assert [o.attr("float32") for o in main.global_block().ops
            if o.type == "mul"] == [None, True, True]
