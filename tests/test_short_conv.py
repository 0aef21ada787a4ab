"""The gated short convolution (ISSUE 49, ``ops/short_conv.py``) at
small sizes on the CPU: the op against a loop over t in numpy, float32
and bfloat16, three taps and four, from the first row; its gradients
against ``jax.grad`` of the loop; the Program op through an executor;
its lowering's count; and the taps it shares with ``ssm_conv``, whose
values stay bit for bit what they were.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.monitor import metrics
from paddle_tpu.ops import selective_scan as SS
from paddle_tpu.ops import short_conv as SC


def _r(*shape, seed=0, scale=0.5):
    return np.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                      np.float32)


def _loop(x, w):
    """``C_t * sum_i w[i] (B X)_(t - K + 1 + i)`` a row at a time, in
    numpy float64: x [B, T, 3C] holds B, C, X side by side."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    c, k = x.shape[-1] // 3, w.shape[0]
    u = x[..., :c] * x[..., 2 * c:]
    out = np.zeros(x.shape[:2] + (c,))
    for t in range(x.shape[1]):
        for i in range(k):
            if t - k + 1 + i >= 0:
                out[:, t] += w[i] * u[:, t - k + 1 + i]
    return out * x[..., c:2 * c]


def _loop_jnp(x, w):
    """The same in ``jax.numpy``, a row at a time, for ``jax.grad``."""
    c, k = x.shape[-1] // 3, w.shape[0]
    u = x[..., :c] * x[..., 2 * c:]
    rows = []
    for t in range(x.shape[1]):
        rows.append(sum(w[i] * u[:, t - k + 1 + i] for i in range(k)
                        if t - k + 1 + i >= 0))
    return jnp.stack(rows, 1) * x[..., c:2 * c]


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-6),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_op_is_the_loop_from_the_first_row(taps, dtype, tol):
    """Each channel by itself, zeros before the sequence: the first K -
    1 rows see one .. K - 1 products; float32 inside whatever x is, x's
    dtype out; and a row never sees a later one."""
    x = jnp.asarray(_r(2, 9, 3 * 5, seed=1), dtype)
    w = jnp.asarray(_r(taps, 5, seed=2))
    got = SC.gated_short_conv(x, w)
    assert got.dtype == dtype and got.shape == (2, 9, 5)
    want = _loop(x.astype(jnp.float32), w)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol)
    # the first rows are what they are alone
    np.testing.assert_allclose(
        np.asarray(got[:, 0], np.float32),
        np.asarray((x[..., 5:10] * x[..., :5] * x[..., 10:])[:, 0],
                   np.float32) * np.asarray(w[-1]), atol=tol)
    later = x.at[:, 5:].set(3.0)
    np.testing.assert_array_equal(SC.gated_short_conv(later, w)[:, :5],
                                  got[:, :5])


@pytest.mark.parametrize("taps", [3, 4])
def test_the_gradients_are_the_loops(taps):
    x, w = jnp.asarray(_r(2, 7, 3 * 4, seed=3)), jnp.asarray(
        _r(taps, 4, seed=4))
    dy = jnp.asarray(_r(2, 7, 4, seed=5))
    loss = lambda fn: lambda x, w: jnp.sum(fn(x, w) * dy)
    got = jax.jit(jax.grad(loss(SC.gated_short_conv), (0, 1)))(x, w)
    want = jax.jit(jax.grad(loss(_loop_jnp), (0, 1)))(x, w)
    for name, g, ref in zip(("dX", "dFilter"), got, want):
        assert float(jnp.max(jnp.abs(ref))) > 1e-2, name
        np.testing.assert_allclose(g, ref, atol=1e-6, err_msg=name)


def test_the_parts_have_to_match_the_filter():
    with pytest.raises(ValueError, match="three parts"):
        SC.gated_short_conv(jnp.zeros((1, 4, 10)), jnp.zeros((3, 3)))
    with pytest.raises(ValueError, match="three parts"):
        SC.gated_short_conv(jnp.zeros((1, 4, 12)), jnp.zeros((3, 3)))


def test_the_program_op_and_its_count():
    """``layers.gated_short_conv`` through an executor: the parameter
    ``<name>_w`` [K, C], the result the op's own function of it, one
    count a lowering under its taps and channels."""
    counter = metrics.registry().get("ptpu_short_conv_lowerings_total")
    key = tuple({"taps": "3", "channels": "6"}[n]
                for n in counter.label_names)
    before = counter.snapshot().get(key, 0)
    main, startup, scope = fluid.Program(), fluid.Program(), fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = layers.data("x", [8, 18], dtype="float32")
        out = layers.gated_short_conv(x, 3, name="op")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = _r(2, 8, 18, seed=6)
        (got,) = exe.run(main, feed={"x": feed}, fetch_list=[out])
        w = np.asarray(scope.find_var("op_w"))
    assert [op.type for op in main.global_block().ops] == ["gated_short_conv"]
    assert w.shape == (3, 6) and np.abs(w).max() <= 3 ** -0.5
    assert tuple(out.shape)[1:] == (8, 6)
    np.testing.assert_allclose(got, _loop(feed, w), atol=1e-6)
    assert counter.snapshot()[key] == before + 1


def test_ssm_conv_is_bit_for_bit_what_it_was():
    """``causal_conv_silu`` through the shared taps against its own
    lines as they stood before ISSUE 49 (bias first, then the taps in
    order), float32 and bfloat16, and by the digest of its result."""
    def as_it_was(x, w, bias):
        f32 = jnp.float32
        k, t = w.shape[0], x.shape[1]
        x32 = jnp.pad(x.astype(f32), [(0, 0), (k - 1, 0), (0, 0)])
        out = bias.astype(f32)
        for i in range(k):
            out = out + w[i].astype(f32) * x32[:, i:i + t]
        return jax.nn.silu(out).astype(x.dtype)

    w, b = jnp.asarray(_r(4, 6, seed=2)), jnp.asarray(_r(6, seed=3))
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(_r(2, 9, 6, seed=1), dtype)
        got, was = SS.causal_conv_silu(x, w, b), as_it_was(x, w, b)
        assert got.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(was, np.float32))
        np.testing.assert_array_equal(
            np.asarray(jax.jit(SS.causal_conv_silu)(x, w, b), np.float32),
            np.asarray(jax.jit(as_it_was)(x, w, b), np.float32))
    x = jnp.asarray(_r(2, 9, 6, seed=1))
    digest = hashlib.sha256(np.asarray(
        SS.causal_conv_silu(x, w, b)).tobytes()).hexdigest()
    assert digest == DIGEST, digest


# of the parent commit's result on these inputs (my CPU run, PR 49)
DIGEST = ("5e8b1139245f82ff0e845783347ff9b5"
          "090adffd6efe13be967158144b442854")
