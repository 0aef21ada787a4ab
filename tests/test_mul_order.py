"""The Program op `mul` flattens its X to ``[rows, K]`` BEFORE it casts
it (ops/math.py `_mul`: a flatten, then `mul_rows`; ISSUE 61). Cast as
``[B, T, K]`` at B over 1 and flattened after, the chip's compiler does
not fuse what made X into the product that reads it: the FFN's hidden
value is written in float32, relaid T-minor by a `copy` and read again,
11.5 ms of `opt350m_train`'s 182 ms step (PERF.md section 6, PR 61). A
cast and a reshape commute exactly, so against the order
the repo had through PR 60, written out here as the reference: the
op's forward, dX and dW bit for bit on the CPU in every case; no value
of rank over 2 cast anywhere in the op's jaxpr, so the order cannot
drift back unnoticed; and one post-LN block's train step compiled for a
described v5e holds no `copy` of a stream-sized value, where the same
step under the reference order holds one."""

import functools
import math
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

import paddle_tpu as fluid                                  # noqa: E402
from paddle_tpu import amp, layers                          # noqa: E402
from paddle_tpu.core import registry, unique_name           # noqa: E402
from paddle_tpu.core.executor import _normalize_feeds       # noqa: E402
from paddle_tpu.layers.state_space import tied_head         # noqa: E402
from test_recompute_kinds import abstract_state             # noqa: E402
from tpu_compile_test import chip, topo                     # noqa: E402,F401

B, T, K, N = 3, 8, 16, 24


def _cast_then_flatten(ctx, op):
    """`mul` as the repo lowered it through PR 60: both operands cast
    as they come, X as ``[B, T, K]``, and flattened after."""
    x, y = ctx.in1(op, "X"), ctx.in1(op, "Y")
    float32 = op.attr("float32", False)
    out_dtype = jnp.float32 if float32 else x.dtype
    x, y = (x.astype(jnp.float32), y.astype(jnp.float32)) if float32 \
        else amp.maybe_bf16(x, y)
    xn, yn = op.attr("x_num_col_dims", 1), op.attr("y_num_col_dims", 1)
    x2 = x.reshape(math.prod(x.shape[:xn]), -1)
    y2 = y.reshape(math.prod(y.shape[:yn]), -1)
    if op.attr("transpose_Y", False):
        y2 = y2.T
    if float32:
        out = jnp.matmul(x2, y2, precision=jax.lax.Precision.HIGHEST)
    else:
        wide = x2.dtype == jnp.bfloat16 or amp.amp_enabled()
        out = amp.amp_out(jnp.matmul(
            x2, y2, preferred_element_type=jnp.float32 if wide else None),
            out_dtype)
    ctx.note(mkn=x2.shape + out.shape[1:], operand_dtype=str(x2.dtype))
    columns = out.shape[1:] if op.attr("transpose_Y", False) else y.shape[yn:]
    ctx.set_out(op, "Out", out.reshape(x.shape[:xn] + columns))


# id: (AMP on, X's shape and dtype, Y's, what builds the op of x and y)
_CASES = {
    "float32": (False, ((B, T, K), "float32"), ((K, N), "float32"),
                functools.partial(layers.mul, x_num_col_dims=2)),
    "amp_bf16": (True, ((B, T, K), "float32"), ((K, N), "float32"),
                 functools.partial(layers.mul, x_num_col_dims=2)),
    "bf16_stream": (True, ((B, T, K), "bfloat16"), ((K, N), "float32"),
                    functools.partial(layers.mul, x_num_col_dims=2)),
    "float32_attr": (True, ((B, T, K), "bfloat16"), ((K, 1), "float32"),
                     functools.partial(layers.mul, x_num_col_dims=2)),
    "transpose_Y": (True, ((B, T, K), "float32"), ((N, K), "float32"),
                    tied_head),
    "y_num_col_dims_1": (True, ((B, T, K), "float32"), ((K, 4, 6), "float32"),
                         functools.partial(layers.mul, x_num_col_dims=2,
                                           y_num_col_dims=1)),
    "y_num_col_dims_2": (True, ((B, T, K), "float32"), ((4, 4, N), "float32"),
                         functools.partial(layers.mul, x_num_col_dims=2,
                                           y_num_col_dims=2)),
    "rank_4": (True, ((2, B, T, K), "float32"), ((K, N), "float32"),
               functools.partial(layers.mul, x_num_col_dims=3)),
}


def _the_op(case):
    """(the `mul` op of the case's one-op Program, AMP on or off, x, y)"""
    amp_on, (xshape, xdtype), (yshape, ydtype), build = _CASES[case]
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), unique_name.guard("m_"):
        x = main.global_block().create_var(
            name="x", shape=xshape, dtype=xdtype)
        y = main.global_block().create_var(
            name="y", shape=yshape, dtype=ydtype)
        if case == "float32_attr":
            with amp.float32():
                build(x, y)
        else:
            build(x, y)
    op, = [o for o in main.global_block().ops if o.type == "mul"]
    assert bool(op.attr("float32", False)) == (case == "float32_attr")
    rng = np.random.RandomState(sum(map(ord, case)))
    draw = lambda shape, dtype: jnp.asarray(
        rng.randn(*shape).astype(np.float32), dtype)
    return op, amp_on, draw(xshape, xdtype), draw(yshape, ydtype)


def _lowered(lowering, op, said=None):
    """``(x, y) -> Out`` of `op` under `lowering`; what the lowering
    notes for the op ledger goes into `said`."""
    def run(x, y):
        ctx = registry.LowerContext(
            {op.input("X")[0]: x, op.input("Y")[0]: y}, None)
        ctx._op_row = said
        lowering(ctx, op)
        return ctx.env[op.output("Out")[0]]
    return run


def _with_grads(f, x, y):
    """``(x, y, weigh) -> (out, dX, dW)`` of `f` as ONE program, and a
    cotangent `weigh` that differs in every element."""
    def all_three(x, y, weigh):
        out, transposed = jax.vjp(f, x, y)
        return (out,) + transposed(weigh)
    out = jax.eval_shape(f, x, y)
    return all_three, jnp.cos(jnp.arange(
        out.size, dtype=jnp.float32)).reshape(out.shape).astype(out.dtype)


def _bits(a):
    a = np.asarray(a)
    return str(a.dtype), a.shape, a.tobytes()


def _casts_of(lead, jaxpr, found=None):
    """The shapes of the values of rank over 2 and leading dimensions
    `lead` (X's, so Out's and both cotangents': a weight is no stream)
    that a `convert_element_type` reads, the jaxprs inside the jaxpr's
    equations included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        shape = eqn.invars[0].aval.shape if eqn.invars else ()
        if eqn.primitive.name == "convert_element_type" \
                and len(shape) > 2 and shape[:len(lead)] == lead:
            found.append(shape)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _casts_of(lead, inner, found)
    return found


@pytest.mark.parametrize("case", sorted(_CASES))
def test_flatten_then_cast_is_cast_then_flatten_bit_for_bit(case):
    op, amp_on, x, y = _the_op(case)
    now, was = {}, {}
    with amp.amp_guard(amp_on):
        new, weigh = _with_grads(
            _lowered(registry.lookup("mul").lower, op, now), x, y)
        old, _ = _with_grads(_lowered(_cast_then_flatten, op, was), x, y)
        got, want = jax.jit(new)(x, y, weigh), jax.jit(old)(x, y, weigh)
        lead = x.shape[:op.attr("x_num_col_dims")]
        casts = _casts_of(lead, jax.make_jaxpr(new)(x, y, weigh).jaxpr)
        casts_before = _casts_of(
            lead, jax.make_jaxpr(old)(x, y, weigh).jaxpr)
        x_is_cast = x.dtype != (jnp.float32 if op.attr("float32", False)
                                else amp.result_dtype(x.dtype))
    for name, a, b in zip(("Out", "dX", "dW"), got, want):
        assert _bits(a) == _bits(b), name
    assert got[1].shape == x.shape and got[1].dtype == x.dtype
    assert got[2].shape == y.shape and got[2].dtype == y.dtype
    assert now == was and set(now) == {"mkn", "operand_dtype"}
    assert now["mkn"][0] == x.size // K and now["mkn"][1] == K
    # the lowering itself: forward and both transposes cast no stream
    # before it is flat, where the old order cast X and dX as they came
    assert not casts, casts
    assert bool(casts_before) == x_is_cast, casts_before


_BT, _D, _F = (4, 2048), 1024, 4096


def _post_ln_block():
    """One post-LN block of `opt350m_train`'s width at its batch, 4 x
    2,048: a product, the residual add, `layer_norm`, `fc` + ReLU,
    `fc`, the residual add, `layer_norm`, under Adam -> (main, startup,
    loss's name, feeds)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), unique_name.guard("b_"):
        x = layers.data("x", [_BT[1], _D], dtype="float32")
        h = layers.layer_norm(layers.elementwise_add(
            x, layers.fc(x, _D, num_flatten_dims=2)), begin_norm_axis=2)
        f = layers.fc(h, _F, num_flatten_dims=2, act="relu")
        h = layers.layer_norm(layers.elementwise_add(
            h, layers.fc(f, _D, num_flatten_dims=2)), begin_norm_axis=2)
        loss = layers.mean(h)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    return main, startup, loss.name, {
        "x": np.zeros(_BT + (_D,), np.float32)}


@pytest.mark.parametrize("order", ["flatten_then_cast", "cast_then_flatten"])
def test_a_blocks_step_compiled_for_the_chip_copies_no_stream(
        order, chip, monkeypatch):
    """The block's train step under bf16 AMP, compiled for a described
    v5e (5 s; nothing runs): no `copy` of a value of 4 x 2,048 rows.
    Under the old order, the control, the same step holds one (two in
    jaxlib 0.9.0: the FFN's hidden ``f32[4,2048,4096]`` under the bias
    add that made it and its gradient in bf16 under the `mul` that
    reads it, both laid out T-minor, ``{1,2,0}``); were the control to
    stop holding, the case above would say nothing any more."""
    if order == "cast_then_flatten":
        monkeypatch.setattr(registry.lookup("mul"), "lower",
                            _cast_then_flatten)
    main, startup, loss, feeds = _post_ln_block()
    exe = fluid.Executor(fluid.CPUPlace())
    with amp.amp_guard(True):
        state = abstract_state(exe, startup)
        feeds, static_info = _normalize_feeds(feeds)
        on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
        step = jax.jit(exe._build(main, tuple(sorted(feeds)), (loss,),
                                  tuple(sorted(state)), static_info),
                       donate_argnums=(0,))
        text = step.lower({n: on(v) for n, v in state.items()},
                          {n: on(v) for n, v in feeds.items()},
                          on(jax.random.key(0))).compile().as_text()
    copied = [shape for shape in re.findall(
        r"= \w+\[([\d,]*)\]\S* copy\(", text)
        if math.prod(map(int, shape.split(","))) >= math.prod(_BT) * _D]
    assert bool(copied) == (order == "cast_then_flatten"), copied
