"""The flash kernels' walk and block rule (ops/flash_attention.py), in
interpret mode: a major block walked in panels against dense float32
math, the panel edge, the automatic block, the block's width from D and
H, and which backward follows from the blocks. One file of the kernel
family's seven (tests/flash_test.py holds what they share), so that
`--dist loadfile` can give each a worker."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import flash_attention as FA
from flash_test import (_assert_close, _dense, _dense_lse, _draw, _f32,
                        _grads_of, _host32, _qkv)


def test_auto_blocks_divide_non_pow2_t():
    """Auto block sizing must pick a DIVISOR of T (largest <= 1024), so
    T=1536 keeps the fused kernel instead of demoting to dense."""
    path, _, bq, bk = FA._resolve_path(
        jnp.zeros((1, 1, 1536, 128)), None, None, None, "interpret")
    assert bq == 768 and bk == 768
    assert 1536 % bq == 0
    # and the kernel at those blocks matches dense
    q, k, v = _qkv(b=1, h=1, t=1536, d=32, seed=3)
    got = FA.flash_attention(q, k, v, causal=True, force="interpret")
    ref = _dense(q, k, v, True, 32 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-3, rtol=2e-2)


def test_auto_block_degenerate_t_demotes_to_dense(monkeypatch):
    """T with no divisor >= 128 under the auto cap (prime 4099, 2*1031)
    must NOT build a near-T^2 grid of tiny blocks — auto sizing demotes
    to the dense path; explicit block sizes still honor the caller."""
    monkeypatch.setattr(FA, "_on_tpu", lambda x: True)

    def path_for(t, block=None):
        q = jnp.zeros((1, 1, t, 64), jnp.float32)
        return FA._resolve_path(q, None, block, block, None)[0]

    assert path_for(2048) == "pallas"        # sanity: clean T stays fused
    assert path_for(4099) == "dense"         # prime
    assert path_for(2 * 1031) == "dense"     # largest divisor 2
    assert path_for(17 * 127) == "dense"     # largest divisor 127 < 128
    assert path_for(2062, block=1031) == "pallas"  # explicit block wins


# -- the walk inside a major block (PR 25) ----------------------------------
# (T, D, panel target, block_q, block_k, cap on an unmasked panel's
# scores or None for the module's) -> the panel edge it gives
_WALKS = [
    # one major block, the whole of T, cut into 4 panels of 128 on the
    # diagonal: the tiles above it never computed, the mask on the
    # diagonal's tiles only, the forward with nothing to rescale (one
    # key block), dk/dv's panels by key with the scores transposed
    pytest.param(512, 64, 128, 512, 512, None, 128, id="T512-one_block"),
    # 2 x 2 major blocks of two panels: the block below the diagonal in
    # one unmasked panel, the block above it skipped, scratch and the
    # running max carried between grid steps; a scale that is no power
    # of two
    pytest.param(512, 32, 128, 256, 256, None, 128, id="T512-four_blocks"),
    # the same with unmasked panels capped at 128 x 256 scores: the
    # block below the diagonal (and every block of the full case) is
    # cut into two
    pytest.param(512, 64, 128, 256, 256, 128 * 256, 128,
                 id="T512-capped_panels"),
    # unequal blocks cross the diagonal anywhere: one panel masked at
    # the offset the grid step gives
    pytest.param(768, 64, 256, 256, 384, None, 256, id="T768-unequal"),
]


def _walk_inputs(t, d, dtype, seed):
    rng = np.random.RandomState(seed)
    q, k, v, dy = (_draw(rng, (1, 2, t, d), dtype) for _ in range(4))
    return q, k, v, dy, _draw(rng, (1, 2, t))


@pytest.mark.parametrize("t, d, tile, bq, bk, scores, edge", _WALKS)
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_tiled_walk_matches_dense(monkeypatch, dtype, causal, t, d, tile,
                                  bq, bk, scores, edge):
    """out, lse, dq, dk, dv of the kernels walking their major blocks
    in panels, against dense float32 math on the same inputs."""
    monkeypatch.setattr(FA, "_TILE", tile)
    if scores:
        monkeypatch.setattr(FA, "_PANEL_SCORES", scores)
    assert FA._tile(bq, tile) == edge
    q, k, v, dy, _ = _walk_inputs(t, d, dtype, seed=5)
    scale = d ** -0.5
    kw = dict(causal=causal, force="interpret", block_q=bq, block_k=bk)

    weigh = lambda o: (_f32(o) * _f32(dy)).sum()
    # each side as ONE program: (out, lse) by the lse entry, the
    # gradients through the entry that gives the output alone
    (o_ref, lse_ref), g_ref = jax.jit(lambda q, k, v: (
        _dense_lse(q, k, v, causal, scale), jax.grad(
            lambda *a: weigh(_dense(*a, causal, scale)), (0, 1, 2))(q, k, v))
    )(*_host32(q, k, v))
    (o, lse), g = jax.jit(lambda q, k, v: (
        FA.flash_attention_lse(q, k, v, **kw), jax.grad(
            lambda *a: weigh(FA.flash_attention(*a, **kw)), (0, 1, 2))(
                q, k, v)))(q, k, v)
    assert o.dtype == dtype and lse.dtype == jnp.float32
    assert lse.shape == q.shape[:3]
    if dtype == jnp.float32:      # today's tolerances
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=2e-3, rtol=2e-2)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   atol=2e-3, rtol=2e-2)
        tol = 5e-3
    else:                         # bf16 out and grads round at 2^-9
        _assert_close("out", o, o_ref, 1e-2)
        _assert_close("lse", lse, lse_ref, 1e-2)
        tol = 2e-2
    for name, a, b in zip(("dq", "dk", "dv"), g, g_ref):
        assert a.dtype == dtype
        _assert_close(name, a, b, tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_tiled_walk_lse_cotangent(monkeypatch, dtype):
    """flash_attention_lse with a NON-ZERO lse cotangent (what ring
    attention sends back) through the tiled backward kernels."""
    monkeypatch.setattr(FA, "_TILE", 128)
    t, d = 512, 64
    q, k, v, dy, dlse = _walk_inputs(t, d, dtype, seed=6)
    scale = d ** -0.5

    def loss(att):
        def f(q, k, v):
            o, lse = att(q, k, v)
            return (_f32(o) * _f32(dy)).sum() + (lse * dlse).sum()
        return f

    g_ref = _grads_of(loss(lambda q, k, v: _dense_lse(q, k, v, True, scale)),
                      _f32(q), _f32(k), _f32(v))
    g = jax.grad(loss(lambda q, k, v: FA.flash_attention_lse(
        q, k, v, causal=True, force="interpret")), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g, g_ref):
        _assert_close(name, a, b, 5e-3 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("block, target, want", [
    (1024, 256, 256), (2048, 512, 512), (768, 512, 384), (1536, 512, 512),
    (384, 256, 128), (1031, 256, 1031), (128, 256, 128), (64, 256, 64)])
def test_panel_edge_divides_its_block(block, target, want):
    """A panel's edge is a multiple of 128 that divides the major
    block, or the block itself where it has no such divisor."""
    assert FA._tile(block, target) == want


@pytest.mark.parametrize("t, d, dtype, want", [
    (2048, 64, jnp.bfloat16, 2048),    # the benchmark's cell: one block
    (2048, 128, jnp.bfloat16, 2048),
    (1536, 128, jnp.bfloat16, 1536),
    (1024, 64, jnp.float32, 1024),
    (2048, 64, jnp.float32, 1024),     # float32 operands: streamed
    (4096, 64, jnp.bfloat16, 1024),    # too long for one block: streamed
    (2048, 256, jnp.bfloat16, 1024),
    (1536, 128, jnp.float32, 768),
    (2062, 64, jnp.bfloat16, 2)])      # no panel divides it: degenerate
def test_auto_block_is_all_of_t_where_it_fits(t, d, dtype, want):
    assert FA._auto_block(t, d, jnp.dtype(dtype).itemsize) == want


@pytest.mark.parametrize("h, d, t, dtype, block, want", [
    (16, 64, 2048, jnp.bfloat16, None, "pallas"),   # the benchmark's cell
    (16, 128, 4096, jnp.bfloat16, None, "pallas"),  # g 1, streamed
    (2, 32, 2048, jnp.float32, None, "pallas"),     # whole width, 64 lanes
    (3, 64, 1024, jnp.bfloat16, None, "pallas"),    # whole width, 192 lanes
    (16, 80, 1024, jnp.bfloat16, None, "dense"),    # 1280 lanes: no block
    (1, 192, 768, jnp.float32, None, "pallas"),     # one head: as before
    (16, 60, 1024, jnp.bfloat16, None, "dense"),    # D no multiple of 8
])
def test_block_width_follows_from_d_and_h(monkeypatch, h, d, t, dtype,
                                          block, want):
    monkeypatch.setattr(FA, "_on_tpu", lambda x: True)
    q = jnp.zeros((1, h, t, d), dtype)     # the heads' shape
    assert FA._resolve_path(q, None, block, block, None)[0] == want


@pytest.mark.parametrize("t, w, block, want", [
    (2048, 128, 2048, "fused"),          # the benchmark's cell
    (512, 128, 1024, "fused"),           # a block no longer than T
    (4096, 128, 1024, "fused_streamed"),     # OLMoE's: streamed
    (2048, 128, 1024, "fused_streamed"),     # float32 at T 2048
    (512, 192, 512, "fused"),            # all of H*D, 192 lanes
    (1024, 192, 1024, "fused_streamed"),     # the same clamped to 512 rows
    (16384, 128, 1024, "fused_streamed"),    # Trinity's: 16 MiB resident
    (32768, 128, 1024, "fused_streamed"),    # the last T within the bound
    (65536, 128, 1024, "two_kernels"),   # ring attention's longest shards
    (16384, 512, 512, "two_kernels"),    # all of H*D, 512 lanes: 64 MiB
])
def test_backward_follows_from_the_blocks(t, w, block, want):
    """One kernel exactly where the backward's blocks, after the VMEM
    clamp of wide blocks, hold all of T; the ONE streamed kernel where
    dq for all rows of a block of heads (float32, and its output block
    twice) is within _RESIDENT_DQ_BYTES; the two kernels beyond: no
    flag decides it."""
    assert FA._backward_of(t, w, block, block) == want
    bq, bk = FA._backward_blocks(t, w, block, block)
    assert t % bq == 0 and t % bk == 0
