"""An expert of TWO matrices, ``w_down relu(w_up x)^2`` (ISSUE 62): the
ungated form of the dropless expert layer (parallel/moe.py) against a
dense loop over the held experts, values, every gradient and the count
of the hidden units the ReLU leaves on; through the Program op with no
``w_gate`` at all; what the recompute plan prices it at; and the gated
layer's Program as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.parallel import moe

N, D, F, E, HELD, FIRST, K = 64, 16, 24, 8, 4, 2, 3


@pytest.fixture(scope="module")
def operands():
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    return dict(
        x=jax.random.normal(keys[0], (N, D)),
        router=jax.random.normal(keys[1], (D, E)) * 0.5,
        w_up=jax.random.normal(keys[2], (HELD, D, F)) * D ** -0.5,
        w_down=jax.random.normal(keys[3], (HELD, F, D)) * F ** -0.5,
        bias=jax.random.normal(keys[4], (E,)) * 0.1,
        weight=jax.random.normal(keys[5], (N, D)))


def _layer(o, x, router, w_up, w_down, force=None):
    out, _, counts, experts, on = moe.routed_experts(
        x, router, None, w_up, w_down, E, first_expert=FIRST, top_k=K,
        norm_topk=True, score="sigmoid", bias=o["bias"], scaling=2.5,
        shared_expert=True, activation="relu2", count_gate=True,
        norm_eps=1e-20, force=force)
    return out, (counts, experts, on)


def _dense(o, x, router, w_up, w_down):
    """Every held expert on every row: sigmoid scores, the top-k of
    score + bias, the unbiased scores over (their sum + 1e-20) times
    2.5."""
    score = jax.nn.sigmoid(x @ router)
    _, chosen = jax.lax.top_k(score + o["bias"], K)
    w = jnp.take_along_axis(score, chosen, 1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * 2.5
    out, on = jnp.zeros_like(x), 0
    for e in range(HELD):
        w_e = jnp.sum(jnp.where(chosen == e + FIRST, w, 0.0), -1)
        hidden = x @ w_up[e]
        out = out + w_e[:, None] * (jnp.square(jax.nn.relu(hidden))
                                    @ w_down[e])
        on = on + jnp.sum((hidden > 0) & (w_e > 0)[:, None])
    return out, on


def test_the_ungated_layer_is_the_dense_loop(operands):
    o = operands
    args = o["x"], o["router"], o["w_up"], o["w_down"]
    out, (counts, experts, on) = _layer(o, *args)
    want, want_on = _dense(o, *args)
    np.testing.assert_allclose(out, want, atol=1e-5)
    assert int(on) == int(want_on) and 0 < int(on) < N * K * F
    assert int(counts.sum()) == N * K and experts.shape == (N, K)
    got = jax.grad(lambda *a: jnp.sum(_layer(o, *a)[0] * o["weight"]),
                   argnums=range(4))(*args)
    dense = jax.grad(lambda *a: jnp.sum(_dense(o, *a)[0] * o["weight"]),
                     argnums=range(4))(*args)
    for name, g, w in zip(("x", "router", "w_up", "w_down"), got, dense):
        np.testing.assert_allclose(
            g, w, atol=2e-6 * float(jnp.abs(w).max()), err_msg="d" + name)


def test_the_row_kernel_serves_it_too(operands):
    """The Pallas scatter-add of a chunk's rows (interpreted here) under
    the ungated experts, forward and backward."""
    o = operands
    wide = lambda a: jnp.tile(a, (1, 8))               # d 128: lane tiles
    x = wide(o["x"])
    router = jnp.tile(o["router"], (8, 1)) / 8
    w_up = jnp.tile(o["w_up"], (1, 8, 1)) / 8
    w_down = jnp.tile(o["w_down"], (1, 1, 8))
    f = lambda force: lambda *a: jnp.sum(
        _layer(o, *a, force=force)[0] * wide(o["weight"]))
    args = x, router, w_up, w_down
    for g, w in zip(jax.grad(f("interpret"), argnums=range(4))(*args),
                    jax.grad(f("xla"), argnums=range(4))(*args)):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()))


def test_a_gate_wants_its_matrix_and_relu2_none(operands):
    o = operands
    with pytest.raises(ValueError, match="ungated one's"):
        moe.routed_experts(o["x"], o["router"], None, o["w_up"],
                           o["w_down"], E, activation="silu")
    with pytest.raises(ValueError, match="ungated one's"):
        moe.routed_experts(o["x"], o["router"], o["w_up"], o["w_up"],
                           o["w_down"], E, activation="relu2")


def _program(activation, **kw):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8, D])
        out, _, _, _ = layers.routed_experts(
            x, E, HELD, FIRST, K, F, True, name="moe",
            activation=activation, **kw)
        fluid.optimizer.SGD(0.1).minimize(layers.reduce_mean(
            layers.square(out)))
    return main, startup


def test_the_program_op_has_no_gate_matrix_and_counts_its_relu():
    """``activation="relu2"``: parameters router, w_up, w_down and no
    w_gate; the op has no WGate input; a train run adds to
    ``moe.gate_on`` the units on and the units there were, and one to
    ``moe.steps``."""
    main, startup = _program("relu2", score_func="sigmoid",
                             routed_scaling_factor=2.5,
                             bias_update_rate=1e-3, shared_expert=True,
                             norm_topk_eps=1e-20)
    names = sorted(p.name for p in main.global_block().all_parameters())
    assert names == ["moe.router", "moe.w_down", "moe.w_up"]
    (op,) = [o for o in main.global_block().ops
             if o.type == "routed_experts"]
    assert not op.input("WGate") and op.attr("activation") == "relu2"
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = {"x": np.random.RandomState(0).randn(4, 8, D).astype("f")}
        before = np.array(scope.find_var("moe.w_up"))
        exe.run(main, feed=feed)
        exe.run(main, feed=feed)
        on, units = np.array(scope.find_var("moe.gate_on"))
        load = np.array(scope.find_var("moe.load"))
        assert np.array(scope.find_var("moe.steps")).tolist() == [2]
        assert load.sum() == 2 * 32 * K
        assert units == load[FIRST:FIRST + HELD].sum() * F
        assert 0.3 < on / units < 0.7           # a fresh ReLU: half
        assert np.abs(np.array(scope.find_var("moe.w_up")) - before).max() > 0
        assert np.abs(np.array(scope.find_var("moe.bias"))).max() \
            == pytest.approx(2e-3)


def test_the_gated_layers_program_is_as_it_was():
    """The gated form's parameters in the order they were made, its
    op's inputs in the order they were given: a gated cell's Program
    (and with it its plan and its op ledger) is byte for byte the
    parent's (tests/test_recompute_visits.py pins the digests)."""
    main, _ = _program("silu")
    assert [p.name for p in main.global_block().all_parameters()] == [
        "moe.router", "moe.w_gate", "moe.w_up", "moe.w_down"]
    (op,) = [o for o in main.global_block().ops
             if o.type == "routed_experts"]
    assert list(op.inputs) == ["X", "RouterW", "WGate", "WUp", "WDown",
                               "Load"]
    assert "activation" not in op.attrs
