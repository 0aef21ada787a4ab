"""Differential attention through the flash kernels (ISSUE 40), in
interpret mode on the CPU: 8 query and 4 key/value heads of 64 with
values of 128 against the two softmaxes in dense float32 math, full and
under a window, the lowering's labels, and `diff_heads`. One file of
the flash kernels' family (tests/test_flash_*.py); the model that joins
the two softmaxes is tests/test_hybrid_ssm.py's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import flash_attention as FA
from flash_test import (_assert_within, _draw, _host32, _traced_once,
                        _with_grads)


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


# T 256 in four streamed blocks of 64 (four, so that a band of two or
# three blocks leaves a block below it unvisited): a window under a
# block, of one block, no multiple of a block; none; all of T in one block
_DIFF = [(256, 64, 50, "window_under_a_block"),
         (256, 64, 64, "window_of_a_block"),
         (256, 64, 100, "window_no_multiple_of_a_block"),
         (256, 64, 0, "full"), (256, None, 0, "full_one_block"),
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
    mk = lambda n, s, *lead: _draw(np.random.RandomState(s),
                                   lead + (1, t, n * d), dtype)
    q, k, v, dy = mk(h, t + window), mk(hkv, 1), mk(hkv, 2), mk(h, 3, 2)
    kw = dict(window=window or None, block_q=block, block_k=block)
    both = lambda fn: lambda *a: jnp.stack(fn(*a))
    run = both(lambda q, k, v: FA.flash_diff_bthd(
        q, k, v, h, hkv, force="interpret", **kw))
    dense = both(lambda q, k, v: FA.flash_diff_bthd(
        q, k, v, h, hkv, force="dense", **kw))
    want = both(lambda q, k, v: _two_softmaxes(q, k, v, h, hkv, window))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    close = functools.partial(_assert_within, tol=tol)
    weigh = lambda o: (o.astype(jnp.float32) * dy.astype(jnp.float32)).sum()
    # out and gradients of the kernels, the dense form and the equations
    # as ONE program each, the kernels' traced once
    eqns, (o, grads) = _traced_once(_with_grads(run, weigh), q, k, v)
    o_dense, g_dense = jax.jit(_with_grads(dense, weigh))(q, k, v)
    o_want, truth = jax.jit(_with_grads(want, weigh))(*_host32(q, k, v))
    assert o.shape == (2, 1, t, h * d) and o.dtype == dtype
    close("out", o, o_want)
    close("dense out", o_dense, o_want)
    assert sorted(eqn.params["name"] for eqn in eqns) \
        == ["flash_bwd"] * 2 + ["flash_fwd"] * 2
    if block:     # streamed: the key axis holds the band's steps alone
        steps = t // block
        if window:
            steps = min(-(-(window - 1) // block) + 1, steps)
        assert tuple(eqns[0].params["grid_mapping"].grid)[1:] \
            == (t // block, steps)
    for name, a, b, c in zip(("dq", "dk", "dv"), grads, truth, g_dense):
        assert a.shape == b.shape and a.dtype == dtype
        close(name, a, b)
        close("dense " + name, c, b)


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
