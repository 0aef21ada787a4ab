"""The selective scan's chunked kernel pair (ISSUE 40) in interpret mode
on the CPU against the plain ``lax.scan`` form: ``y`` and the gradients
of all six inputs, at sequence lengths and channel counts that are and
are not multiples of the chunk and the group, bf16 and float32
operands, step sizes that make the state decay to nothing or hardly at
all; the dispatch and its counter; the ops round the scan.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops import selective_scan as SS
from flash_test import _with_grads

F32 = jnp.float32


def _operands(b, t, c, n, dtype, dt_bias=0.0, seed=0):
    """s, dt, A, B, C, D and a cotangent for y, drawn and rounded to
    `dtype` on the host: no program is compiled for them."""
    rng = np.random.RandomState(seed)
    draw = lambda *shape: rng.randn(*shape).astype(np.float32)
    cast = lambda x, dt=F32: jnp.asarray(x.astype(jnp.dtype(dt)))
    return (cast(draw(b, t, c), dtype),
            cast(np.logaddexp(draw(b, t, c) + np.float32(dt_bias), 0),
                 dtype),                                    # softplus
            cast(-np.exp(draw(c, n) * np.float32(0.5))),
            cast(draw(b, t, n), dtype), cast(draw(b, t, n), dtype),
            cast(draw(c)), cast(draw(b, t, c)))


@functools.lru_cache(maxsize=None)
def _y_and_gradients(chunk=None, group=None):
    """(w, the six operands) -> (y, d sum(y w) / d each) as one compiled
    program, the forward run once: of the kernels at a chunk and a
    group, of the float32 step form without. Kept, so that cases of one
    shape share what they compile."""
    if chunk:
        fn = lambda *a: SS.selective_scan(*a, chunk=chunk, group=group,
                                          force="interpret")
    else:
        fn = lambda *a: SS.scan_steps(*(x.astype(F32) for x in a))

    return jax.jit(lambda w, *ops: _with_grads(
        fn, lambda y: jnp.sum(y.astype(F32) * w))(*ops))


# (B, T, C, chunk, group): T a multiple of the chunk and not, under one
# chunk, C a multiple of the group and not, one group and several
_SHAPES = [(2, 48, 256, 16, 128, "whole_chunks_two_groups"),
           (1, 40, 200, 16, 128, "t_and_c_no_multiples"),
           (2, 10, 128, 32, 128, "t_under_one_chunk"),
           (1, 64, 384, 32, 256, "c_one_and_a_half_groups"),
           (1, 33, 64, 16, 256, "c_under_one_group")]


# every shape in both dtypes at mixed step sizes; the two extremes of
# the step size at the shape that has several chunks and groups
_CASES = [pytest.param(dtype, 0.0, *s[:5], id="%s-dt_mixed-%s" % (name, s[5]))
          for s in _SHAPES
          for name, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16))]
_CASES += [pytest.param(dtype, bias, *_SHAPES[0][:5],
                        id="%s-%s" % (name, what))
           for what, bias in (("dt_large", 3.0), ("dt_small", -6.0))
           for name, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16))]


@pytest.mark.parametrize("dtype, dt_bias, b, t, c, chunk, group", _CASES)
def test_kernels_match_the_step_form(dtype, dt_bias, b, t, c, chunk, group):
    """``dt_large``: steps near 3, decays exp(-3 A) near 0, the state
    is its last input alone; ``dt_small``: steps near 2.5e-3, decays
    near 1, the state sums the whole sequence."""
    *ops, w = _operands(b, t, c, 16, dtype, dt_bias, seed=t + c)
    (y, grads), (want, wants) = (side(w, *ops) for side in (
        _y_and_gradients(chunk, group), _y_and_gradients()))
    assert y.shape == (b, t, c) and y.dtype == dtype
    tol = 1e-5 if dtype == F32 else 1.5e-2
    f32 = lambda x: np.asarray(x).astype(np.float32)
    close = lambda name, got, ref: np.testing.assert_allclose(
        f32(got), f32(ref), err_msg=name,
        atol=tol * max(float(np.max(np.abs(f32(ref)))), 1e-3))
    close("y", y, want)
    for name, got, ref, op in zip(("ds", "ddt", "dA", "dB", "dC", "dD"),
                                  grads, wants, ops):
        assert got.shape == op.shape and got.dtype == op.dtype, name
        close(name, got, ref)


def test_the_state_is_float32_whatever_the_operands():
    """The kernels on bf16-valued operands are the float32 step form on
    the same values to its last bits but for y's own rounding; the step
    form with its state HELD in bf16 between steps is a hundred times
    further off than the kernels in float32."""
    *ops, _ = _operands(1, 256, 128, 16, jnp.bfloat16, -4.0, seed=3)
    f32 = [x.astype(F32) for x in ops]
    truth = SS.scan_steps(*f32)
    top = float(jnp.max(jnp.abs(truth)))
    err = lambda y: float(jnp.max(jnp.abs(y.astype(F32) - truth))) / top
    run = lambda *a: SS.selective_scan(*a, chunk=64, force="interpret")
    assert err(run(*f32)) < 1e-5
    assert err(run(*ops)) < 4e-3        # y rounded to bf16, once
    assert err(SS.scan_steps(*f32, state_dtype=jnp.bfloat16)) > 1e-3


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


def test_the_kernels_names_grids_and_what_they_hold():
    """One forward and one backward kernel under the names a device
    trace shows; the grid walks (B, chunks, groups); no value of either
    call is [T, C, N]; the state saved is one a chunk."""
    *ops, _ = _operands(2, 64, 256, 16, F32)
    fn = lambda *a: jnp.sum(SS.selective_scan(*a, chunk=16, group=128,
                                              force="interpret"))
    eqns = list(_pallas_eqns(jax.make_jaxpr(
        jax.grad(fn, tuple(range(6))))(*ops).jaxpr))
    assert [e.params["name"] for e in eqns] == ["selective_scan_fwd",
                                                "selective_scan_bwd"]
    for eqn in eqns:
        assert tuple(eqn.params["grid_mapping"].grid) == (2, 4, 2)
        for var in list(eqn.invars) + list(eqn.outvars):
            assert int(np.prod(var.aval.shape)) < 2 * 64 * 256 * 16
    assert [v.aval.shape for v in eqns[0].outvars] == [(2, 64, 256),
                                                       (2, 4, 16, 256)]


def test_dispatch_and_its_counter():
    """On the CPU the step form; ``force`` pins the kernels; every
    lowering of a direction counts itself once; sizes off the tiles
    raise."""
    count = lambda **want: sum(
        v for key, v in SS._LOWERINGS.snapshot().items()
        if all(key[SS._LOWERINGS.label_names.index(k)] == x
               for k, x in want.items()))
    *ops, _ = _operands(1, 32, 128, 16, F32)
    steps = dict(path="steps", direction="fwd", chunk="0", d_state="16")
    fwd = dict(path="interpret", direction="fwd", chunk="16", d_state="16")
    bwd = dict(fwd, direction="bwd")
    before = [count(**labels) for labels in (steps, fwd, bwd)]
    np.testing.assert_allclose(SS.selective_scan(*ops),
                               SS.scan_steps(*ops), atol=1e-6)
    jax.grad(lambda *a: jnp.sum(SS.selective_scan(
        *a, chunk=16, force="interpret")))(*ops)
    assert [count(**labels) for labels in (steps, fwd, bwd)] \
        == [n + 1 for n in before]
    for bad in (dict(chunk=24), dict(group=192)):
        with pytest.raises(ValueError, match="multiple"):
            SS.selective_scan(*ops, force="interpret", **bad)


def test_the_program_ops_round_the_scan():
    """``ssm_conv`` -> ``ssm_dt`` -> ``selective_scan`` -> ``ssm_gate``
    as Program ops with their parameters as initialised, against the
    same in ``jax.numpy``; ``gmu_gate`` is the gate's other name."""
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(2, 24, 8).astype("float32"),
            "z": rng.randn(2, 24, 8).astype("float32"),
            "bc": rng.randn(2, 24, 32).astype("float32")}
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = layers.data("x", [24, 8], dtype="float32")
        z = layers.data("z", [24, 8], dtype="float32")
        bc = layers.data("bc", [24, 32], dtype="float32")
        b, c = layers.split(bc, 2, dim=2)
        s = layers.ssm_conv(x, 4, name="m_conv")
        dt = layers.ssm_dt(z, name="m_dt_b")
        y = layers.selective_scan(s, dt, b, c, 16, name="m_scan")
        out = layers.ssm_gate(y, z)
        same = layers.gmu_gate(y, z)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=feed, fetch_list=[s, dt, y, out, same])
        p = {n: np.asarray(scope.find_var(n)) for n in (
            "m_conv_w", "m_conv_b", "m_dt_b", "m_scan_a_log", "m_scan_d")}
    assert p["m_conv_w"].shape == (4, 8) and np.abs(p["m_conv_w"]).max() <= .5
    np.testing.assert_allclose(p["m_scan_a_log"][3], np.log(np.arange(1, 17)),
                               rtol=1e-6)
    np.testing.assert_allclose(p["m_scan_d"], 1.0)
    # softplus of the dt bias spans [1e-3, 1e-1]
    steps = np.log1p(np.exp(p["m_dt_b"]))
    np.testing.assert_allclose([steps.min(), steps.max()], [1e-3, 1e-1],
                               rtol=1e-4)
    want_s = SS.causal_conv_silu(feed["x"], p["m_conv_w"], p["m_conv_b"])
    want_dt = jax.nn.softplus(feed["z"] + p["m_dt_b"])
    want_y = SS.scan_steps(want_s, want_dt, -jnp.exp(p["m_scan_a_log"]),
                           feed["bc"][..., :16], feed["bc"][..., 16:],
                           p["m_scan_d"])
    for g, w in zip(got, (want_s, want_dt, want_y,
                          want_y * jax.nn.silu(feed["z"]),
                          want_y * jax.nn.silu(feed["z"]))):
        np.testing.assert_allclose(g, w, atol=2e-6)
