"""A score of two parts through the ONE streamed backward kernel (ISSUE
56), in interpret mode: latent attention's `q k^T + q2 k2^T`, k2 ONE
key that every head reads, whose backward is flash_bwd wherever the
pair's dq and dq2 can stay in VMEM and flash_bwd_dq + flash_bwd_dkv
beyond that bound. All five gradients (dq, dk, dv, dq2, dk2) against
dense float32 math with the shared key broadcast in the einsum alone,
and against the two kernels' sums; which backward the shapes take; the
kernels' names. One file of the kernel family's (tests/flash_test.py
holds what they share)."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import flash_attention as FA
from flash_test import _assert_close, _draw, _f32, _np32, _traced_once

_D = 128
_NAMES = ("dq", "dk", "dv", "dq2", "dk2")
_ONE = ["flash_fwd", "flash_bwd"]
_TWO = ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]

# (T, block_q, block_k): the blocks the backward walks. A T in one block
# has no kernel of its own under two parts: the streamed one runs it.
# 2 x 2 blocks of 256 are cut into panels of 128 on the diagonal (_TILE
# is 128 here); with one q block to a key block dk and dv finish inside
# their panel, with several they are summed in scratch.
_BLOCKS = [
    pytest.param(256, None, None, id="nq1_nk1"),
    pytest.param(512, 256, 256, id="nq2_nk2_panels"),
    pytest.param(256, 128, 64, id="nq2_nk4"),
    pytest.param(256, 256, 64, id="nq1_nk4"),
]
_SHAPES = [pytest.param(h, d2, id="%d_heads-part_of_%d" % (h, d2))
           for d2 in (64, 128) for h in (2, 4)]
_DTYPES = [pytest.param(jnp.float32, id="f32"),
           pytest.param(jnp.bfloat16, id="bf16")]


def _scale(d2):
    return (_D + d2) ** -0.5 * 1.3


@functools.lru_cache(maxsize=None)
def _dense_side(dtype, t, h, d2):
    """((q, k, v, q2, k2), dy, the dense float32 gradients of
    sum(out * dy)): FA._dense_lse with k2 [B, T, D2] as it is, read by
    every head in the einsum. ONE compiled program a shape."""
    rng = np.random.RandomState(56 + t + h + d2)
    mk = lambda lanes: _draw(rng, (1, t, lanes), dtype, 0.4)
    args = (mk(h * _D), mk(h * _D), mk(h * _D), mk(h * d2), mk(d2))
    dy = mk(h * _D)

    def dense(q, k, v, q2, k2):
        out, _ = FA._dense_lse(
            *(FA.heads_first(x, h) for x in (q, k, v)), True, _scale(d2),
            q2=FA.heads_first(q2, h), k2=k2)
        return (FA.heads_last(out) * _f32(dy)).sum()

    want = jax.jit(jax.grad(dense, tuple(range(5))))(
        *(jnp.asarray(_np32(x)) for x in args))
    return args, dy, want


@functools.lru_cache(maxsize=None)
def _kernel_side(dtype, t, bq, bk, h, d2, bound):
    """(the kernels' names, the five gradients) of the kernels in
    interpret mode under the byte bound `bound` (None: the module's)."""
    args, dy, _ = _dense_side(dtype, t, h, d2)

    def loss(q, k, v, q2, k2):
        out = FA.flash_bthd(q, k, v, h, causal=True, scale=_scale(d2),
                            force="interpret", block_q=bq, block_k=bk,
                            q2=q2, k2=k2)
        return (_f32(out) * _f32(dy)).sum()

    was = FA._RESIDENT_DQ_BYTES
    FA._RESIDENT_DQ_BYTES = was if bound is None else bound
    try:
        eqns, got = _traced_once(jax.grad(loss, tuple(range(5))), *args)
    finally:
        FA._RESIDENT_DQ_BYTES = was
    return [eqn.params["name"] for eqn in eqns], got


@pytest.fixture(autouse=True)
def _panels_of_128(monkeypatch):
    monkeypatch.setattr(FA, "_TILE", 128)


@pytest.mark.parametrize("h, d2", _SHAPES)
@pytest.mark.parametrize("t, bq, bk", _BLOCKS)
@pytest.mark.parametrize("dtype", _DTYPES)
def test_two_part_one_kernel_matches_dense(dtype, t, bq, bk, h, d2):
    """dq, dk, dv, dq2 and dk2 of the ONE kernel against the dense
    float32 gradients: a part of 64 (two heads to q2's 128-lane block,
    each storing its own lanes of the resident dq2; with four heads two
    pairs, so that dk2 is the sum of two partials) and of 128 (a head
    a block, a partial a head)."""
    names, got = _kernel_side(dtype, t, bq, bk, h, d2, None)
    assert names == _ONE
    args, _, want = _dense_side(dtype, t, h, d2)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, x, a, b in zip(_NAMES, args, got, want):
        assert a.shape == x.shape and a.dtype == dtype, name
        assert np.isfinite(_np32(a)).all(), name
        _assert_close(name, a, b, tol)


@pytest.mark.parametrize("h, d2", _SHAPES)
@pytest.mark.parametrize("t, bq, bk", _BLOCKS)
def test_two_part_one_kernel_is_the_two_kernels_sums(t, bq, bk, h, d2):
    """No shape within the byte bound: the same call still runs, as
    flash_bwd_dq + flash_bwd_dkv, and the ONE kernel's gradients are
    their sums in another order: float32's rounding apart (the
    tolerance tests/test_latent_moe.py holds the kernels to)."""
    names, two = _kernel_side(jnp.float32, t, bq, bk, h, d2, 0)
    assert names == _TWO
    _, one = _kernel_side(jnp.float32, t, bq, bk, h, d2, None)
    for name, a, b in zip(_NAMES, one, two):
        assert a.dtype == b.dtype == jnp.float32, name
        _assert_close(name, a, b, 1e-5)


def test_two_parts_not_causal_and_two_sequences():
    """Every score counts (no walk's cut) and a batch of two, whose
    partials of dk2 are summed a sequence at a time: the ONE kernel
    against dense float32 math written out."""
    h, d2, t = 4, 64, 256
    rng = np.random.RandomState(3)
    mk = lambda lanes: _draw(rng, (2, t, lanes), jnp.float32, 0.4)
    args = (mk(h * _D), mk(h * _D), mk(h * _D), mk(h * d2), mk(d2))
    dy = mk(h * _D)

    def kernels(q, k, v, q2, k2):
        return (FA.flash_bthd(q, k, v, h, force="interpret", block_q=128,
                              block_k=64, q2=q2, k2=k2) * dy).sum()

    def dense(q, k, v, q2, k2):
        out, _ = FA._dense_lse(
            *(FA.heads_first(x, h) for x in (q, k, v)), False,
            (_D + d2) ** -0.5, q2=FA.heads_first(q2, h), k2=k2)
        return (FA.heads_last(out) * dy).sum()

    eqns, got = _traced_once(jax.grad(kernels, tuple(range(5))), *args)
    assert [eqn.params["name"] for eqn in eqns] == _ONE
    want = jax.jit(jax.grad(dense, tuple(range(5))))(*args)
    for name, a, b in zip(_NAMES, got, want):
        _assert_close(name, a, b, 1e-5)


@pytest.mark.parametrize("t, dtype, d2, backward", [
    (4096, jnp.bfloat16, 64, "fused_streamed"),     # xing4_train_T4k
    (8192, jnp.bfloat16, 64, "fused_streamed"),     # joyai_train_T8k
    (256, jnp.bfloat16, 64, "fused_streamed"),      # one block: no "fused"
    (8192, jnp.bfloat16, 128, "fused_streamed"),    # a head a block of q2
    (16384, jnp.bfloat16, 64, "two_kernels"),       # 50 MB would stay
    (8192, jnp.float32, 64, "two_kernels"),         # 38 MB in float32
    (32768, jnp.bfloat16, 128, "two_kernels"),
])
def test_which_backward_two_parts_take(t, dtype, d2, backward):
    """From the shapes alone: the pair's dq, both heads' lanes, and dq2
    in float32 with their output blocks twice, against the byte bound
    the one-part kernel has. The lowering counter says the same word."""
    h = 32
    q = jax.ShapeDtypeStruct((1, t, h * _D), dtype)
    q2 = jax.ShapeDtypeStruct((1, t, h * d2), dtype)
    block = FA._auto_block(t, _D, jnp.dtype(dtype).itemsize)
    assert FA._backward2_for(q, q2, h, block, block) == backward
    resident = t * (max(_D * (_D // d2), _D) + 128) * (
        4 + 2 * jnp.dtype(dtype).itemsize)
    assert (resident <= FA._RESIDENT_DQ_BYTES) == (
        backward == "fused_streamed")
    if t > 4096:        # tracing the dispatch alone: nothing is compiled
        return
    labels = dict(path="interpret", entry="bthd", heads_per_block="1",
                  backward=backward, mask="causal", kv_groups="1",
                  key_width=str(_D + d2), value_width=str(_D),
                  second_part="shared", window="0")
    was = FA._LOWERINGS.value(**labels)
    k2 = jax.ShapeDtypeStruct((1, t, d2), dtype)
    jax.eval_shape(lambda *a: FA.flash_bthd(
        a[0], a[0], a[0], h, causal=True, force="interpret", q2=a[1],
        k2=a[2]), q, q2, k2)
    assert FA._LOWERINGS.value(**labels) == was + 1


def test_the_vmem_asked_for_holds_what_stays_resident():
    """_one_kernel_vmem_bytes with a second part: what a one-part call
    of the same blocks asks for, plus the second head's dq, dq2, q2's
    and k2's blocks and dk2's; handed no second part, the number it
    gave before (28 MiB and a panel's room at T 16,384: PR 39)."""
    one = FA._one_kernel_vmem_bytes(8192, 128, 1024, 1024, 1, 2, 2, 2, 1)
    two = FA._one_kernel_vmem_bytes(8192, 128, 1024, 1024, 1, 2, 2, 2, 1,
                                    2, 128)
    more = (8192 * 256 * 8            # a head's dq more, and dq2
            + 2 * 2 * 128 * 2048      # q2's and k2's blocks, twice
            + 3 * 1024 * 128 * 4      # dk2's block twice and its scratch
            + 8 * 4 * (2 * 1024 + 8192))    # a head's statistics more
    assert two - one == more
    assert 24 * 2 ** 20 == 8192 * 384 * 8 < two < 64 * 2 ** 20
    assert FA._one_kernel_vmem_bytes(
        16384, 128, 1024, 1024, 1, 2, 2, 2, 1) == 16384 * 128 * 8 + (
        2 * 128 * 2 * 5 * 1024 + 4 * 1024 * 128 * 2 + 2 * 1024 * 128 * 4
        + 32 * (2 * 1024 + 16384) + 16 * 1024 * 1024)
