"""``silu_mul`` under its written backward (``ops/nn.py``, ISSUE 67):
the forward's result and the two gradients are VALUES (they pass through
``optimization_barrier``, so XLA's TPU pipeline cannot make them again
inside the products that read them) and the residuals are X and Y as
they came.

Held here, on the CPU: the value bit for bit and both gradients against
autodiff of the four lines the op was (float32 tight, bfloat16 within
one ulp of the result's dtype), at widths that are and are not whole
lane tiles and on ``[B, T, F]``; under ``jax.checkpoint`` with the
regions' policy the residuals are the two kept products and nothing
else; the counter's labels. What the TPU's compiler makes of it is
``tests/test_kernel_ledger.py``'s described-v5e case.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops import control_flow as CF
from paddle_tpu.ops import nn as NN


def four_lines(x, y):
    """The op's lowering through PR 66, under autodiff."""
    out = jax.nn.silu(x.astype(jnp.float32)) * y.astype(jnp.float32)
    return out.astype(x.dtype)


def _drawn(shape, dtype, seed):
    draws = np.random.RandomState(seed).randn(3, *shape) * 2.0
    return tuple(jnp.asarray(a, dtype) for a in draws)


def _with_grads(fn):
    """ONE program a side: the value and both gradients under d."""
    def run(x, y, d):
        out, back = jax.vjp(fn, x, y)
        return (out,) + back(d)
    return jax.jit(run)


def _ulps(got, want):
    """The largest distance in units of the last place of `want`'s
    dtype (both arrays of that dtype)."""
    want32, got32 = (np.asarray(a, np.float32) for a in (want, got))
    spacing = np.asarray(jnp.abs(jnp.nextafter(
        want, jnp.asarray(np.inf, want.dtype)).astype(jnp.float32)
        - want.astype(jnp.float32)))
    return float(np.max(np.abs(got32 - want32) / spacing))


_SHAPES = [(16, 256), (8, 200), (2, 8, 384), (3, 5, 72)]


@pytest.mark.parametrize("shape", _SHAPES, ids=lambda s: "x".join(
    map(str, s)))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_value_and_both_gradients_are_autodiffs(shape, dtype):
    x, y, d = _drawn(shape, dtype, seed=len(shape) + shape[-1])
    got = _with_grads(NN.silu_mul)(x, y, d)
    want = _with_grads(four_lines)(x, y, d)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == x.dtype and g.shape == shape
    # the value: today's arithmetic, to the bit
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want[0], np.float32))
    for g, w in zip(got[1:], want[1:]):
        if dtype == "float32":
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-6, atol=1e-6)
        else:       # float32 inside, rounded once: an ulp of bfloat16
            assert _ulps(g, w) <= 1.0


def test_the_gradients_are_the_formulas_in_float64():
    """Against numpy at twice the precision: d y s (1 + x (1 - s)) and
    d x s."""
    x, y, d = _drawn((8, 200), "float32", seed=7)
    _, dx, dy = _with_grads(NN.silu_mul)(x, y, d)
    x64, y64, d64 = (np.asarray(a, np.float64) for a in (x, y, d))
    s = 1.0 / (1.0 + np.exp(-x64))
    np.testing.assert_allclose(np.asarray(dx), d64 * y64 * s
                               * (1 + x64 * (1 - s)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dy), d64 * x64 * s, rtol=1e-5,
                               atol=1e-6)


def _mlp(act, policy):
    """``W_down(act(xn W_gate, xn W_up))`` as a region keeps it: the two
    products named MUL_OUT under `policy`."""
    def region(xn, w_gate, w_up, w_down):
        gate = checkpoint_name(xn @ w_gate, CF.MUL_OUT)
        up = checkpoint_name(xn @ w_up, CF.MUL_OUT)
        return act(gate, up) @ w_down
    region = jax.checkpoint(region, policy=policy)
    return lambda *args: jnp.sum(region(*args).astype(jnp.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_region_saves_x_and_y_and_nothing_of_the_ops_own(dtype):
    """Under the regions' policy the backward reads the region's inputs
    and the two kept products: no float32 ``[T, F]`` and no `hidden` is
    saved, as many bytes are kept as under the four lines, and the
    gradients are theirs."""
    from jax._src.ad_checkpoint import saved_residuals
    t, d_model, f = 32, 16, 200
    rs = np.random.RandomState(3)
    args = tuple(jnp.asarray(rs.randn(*s) * 0.3, dtype) for s in (
        (t, d_model), (d_model, f), (d_model, f), (f, d_model)))
    kept = {}
    for name, act in (("rule", NN.silu_mul), ("four", four_lines)):
        before = CF._KEPT_BYTES.value(name=CF.MUL_OUT)
        saved = saved_residuals(_mlp(act, CF._saves((CF.MUL_OUT,))), *args)
        kept[name] = CF._KEPT_BYTES.value(name=CF.MUL_OUT) - before
        if name == "rule":
            inside = [aval for aval, why in saved if "argument" not in why]
            assert [(a.shape, a.dtype) for a in inside] \
                == [((t, f), jnp.dtype(dtype))] * 2, saved
    itemsize = jnp.dtype(dtype).itemsize
    assert kept["rule"] == kept["four"] == 2 * t * f * itemsize
    got = jax.jit(jax.grad(_mlp(NN.silu_mul, CF._region_policy),
                           (0, 1, 2, 3)))(*args)
    want = jax.jit(jax.grad(_mlp(four_lines, CF._region_policy),
                            (0, 1, 2, 3)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=2e-5 if dtype == "float32" else 0.05,
            atol=1e-5 if dtype == "float32" else 0.05)


def test_outside_a_region_the_residuals_are_the_two_inputs():
    from jax._src.ad_checkpoint import saved_residuals
    x, y, _ = _drawn((8, 256), "bfloat16", seed=5)
    saved = saved_residuals(
        lambda x, y: jnp.sum(NN.silu_mul(x, y).astype(jnp.float32)), x, y)
    wide = [aval for aval, _ in saved if aval.shape == x.shape]
    assert len(wide) == 2 and {a.dtype for a in wide} == {x.dtype}, saved


def test_the_results_are_behind_barriers_in_the_steps_jaxpr():
    """One barrier on the forward's result, one on the two gradients:
    what keeps a reader from making them again."""
    x, y, d = _drawn((8, 128), "bfloat16", seed=9)
    text = str(jax.make_jaxpr(
        lambda x, y, d: jax.vjp(NN.silu_mul, x, y)[1](d))(x, y, d))
    assert text.count("optimization_barrier") == 2, text


def test_each_direction_counts_itself_by_width():
    count = lambda: tuple(NN._SILU_MUL_LOWERINGS.value(
        path="rule", direction=direction, width="328")
        for direction in ("fwd", "bwd"))
    fwd, bwd = count()
    x, y, d = _drawn((4, 328), "float32", seed=11)
    jax.make_jaxpr(NN.silu_mul)(x, y)
    assert count() == (fwd + 1, bwd)
    jax.make_jaxpr(lambda x, y, d: jax.vjp(NN.silu_mul, x, y)[1](d))(x, y, d)
    assert count() == (fwd + 2, bwd + 1)


def test_the_program_op_trains_through_the_rule_under_amp():
    """``layers.silu_mul`` inside a ``layers.recompute`` region under
    bf16 AMP and Adam: the step runs through the rule (the counter says
    so) and the loss falls."""
    before = NN._SILU_MUL_LOWERINGS.value(path="rule", direction="bwd",
                                          width="24")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.amp.amp_guard(True), fluid.program_guard(main, startup), \
            fluid.scope_guard(fluid.Scope()):
        x = layers.data("x", shape=[6, 8], dtype="float32")
        with layers.recompute():
            hidden = layers.silu_mul(
                layers.fc(x, 24, num_flatten_dims=2, bias_attr=False),
                layers.fc(x, 24, num_flatten_dims=2, bias_attr=False))
            out = layers.fc(hidden, 8, num_flatten_dims=2, bias_attr=False)
        loss = layers.mean(layers.square(out - x))
        fluid.optimizer.Adam(1e-2).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"x": np.random.RandomState(0).randn(2, 6, 8).astype(
            np.float32)}
        losses = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
                  for _ in range(20)]
    assert NN._SILU_MUL_LOWERINGS.value(
        path="rule", direction="bwd", width="24") > before
    assert np.isfinite(losses).all() and losses[-1] < 0.8 * losses[0]
