"""The flash kernels' mask forms (ops/flash_attention.py), in
interpret mode: grouped key/value heads under the block-granular
causal mask (ISSUE 32) and block diffusion's own-block form (ISSUE 37),
against dense float32 math with the mask written out. One file of the
kernel family's seven (tests/flash_test.py holds what they share)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import flash_attention as FA
from flash_test import (_assert_close, _dense_block_causal, _f32,
                        _gqa_inputs, _host32, _np32, _traced_once,
                        _with_grads)


# -- grouped key/value heads and the block-granular mask (ISSUE 32) ----------

@pytest.mark.parametrize("mask_block, strict", [
    (1, False), (4, False), (4, True), (32, False), (32, True)],
    ids=["causal", "b4", "b4_strict", "b32", "b32_strict"])
@pytest.mark.parametrize("block", [None, 128], ids=["one_block", "streamed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_grouped_kv_block_causal_matches_dense(dtype, block, mask_block,
                                               strict):
    """4 query heads of 128 reading 2 key/value heads under each mask,
    one block and streamed, in interpret mode against dense float32
    math with the mask written out: out, lse, dq, and dk, dv summed over
    each group, with a non-zero lse cotangent. Under `strict` the first
    block's rows see nothing: their out is finite, their lse -1e30, and
    weighed out (as a merge by lse weighs them) they leave every
    gradient finite and right."""
    h, hkv, d, t = 4, 2, 128, 256
    q, k, v, dy, dlse = _gqa_inputs(h, hkv, d, t, dtype)
    kw = dict(causal=True, force="interpret", block_q=block, block_k=block,
              n_kv_head=hkv, mask_block=mask_block, strict=strict)
    seen = np.arange(t) // mask_block >= int(strict)    # a row sees a key
    assert int((~seen).sum()) == (mask_block if strict else 0)
    w = jnp.asarray(seen, jnp.float32)

    def weigh(outs):
        o, lse = outs
        return (_f32(o) * _f32(dy) * w[None, :, None]).sum() \
            + (jnp.where(seen, lse, 0.0) * dlse).sum()

    # out, lse and the gradients: the kernels' as one program, and the
    # dense form's as one
    (o, lse), got = jax.jit(_with_grads(
        lambda q, k, v: FA.flash_bthd_lse(q, k, v, h, **kw), weigh))(q, k, v)
    (o_ref, lse_ref, seen_ref), want = jax.jit(_with_grads(
        lambda q, k, v: _dense_block_causal(q, k, v, h, hkv, mask_block,
                                            strict),
        lambda outs: weigh(outs[:2])))(*_host32(q, k, v))
    assert (np.asarray(seen_ref) == seen).all()
    assert o.shape == q.shape and lse.shape == (1, h, t)
    o, lse, o_ref, lse_ref = (_np32(x) for x in (o, lse, o_ref, lse_ref))
    assert np.isfinite(o).all()
    tol = 5e-3 if dtype == jnp.float32 else 2e-2
    _assert_close("out", np.where(seen[None, :, None], o, 0), o_ref, tol)
    _assert_close("lse", np.where(seen, lse, 0),
                  np.where(seen, lse_ref, 0), tol)
    assert (np.where(seen, 0, lse) <= 0).all()
    assert (lse[..., ~seen] < -1e29).all()
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == dtype
        assert np.isfinite(_np32(a)).all(), name
        _assert_close(name, a, b, tol)


def test_block_mask_of_one_row_is_causal_bit_for_bit():
    """`mask_block` 1 without `strict` IS causal: the same kernels on
    the same operands, every bit."""
    h, hkv, d, t = 4, 2, 128, 256
    q, k, v, dy, _ = _gqa_inputs(h, hkv, d, t, jnp.bfloat16)
    def grads(**kw):
        f = lambda q, k, v: (_f32(FA.flash_bthd(
            q, k, v, h, causal=True, force="interpret", n_kv_head=hkv,
            block_q=128, block_k=128, **kw)) * _f32(dy)).sum()
        return jax.value_and_grad(f, (0, 1, 2))(q, k, v)
    (a, ga), (b, gb) = grads(), grads(mask_block=1, strict=False)
    assert float(a) == float(b)
    for x, y in zip(ga, gb):
        assert bool((x == y).all())


# -- the own-block form (ISSUE 37): block diffusion inside the kernels -------

@pytest.mark.parametrize("seq, block", [(256, None), (512, 256)],
                         ids=["one_block", "streamed"])
@pytest.mark.parametrize("mask_block", [4, 128], ids=["b4", "b128"])
@pytest.mark.parametrize("h, hkv", [(2, 2), (4, 2), (8, 1)],
                         ids=["group1", "group2", "group8"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_own_block_form_matches_dense(monkeypatch, dtype, h, hkv, mask_block,
                                      seq, block):
    """[noised; clean] rows through ONE call of each kernel, in
    interpret mode against dense float32 math with the 2L x 2L mask
    written out: out, dq, and dk, dv of both halves, summed over each
    group. A half in one block (one masked panel; forward grid
    (., 2, 1)) and in two streamed blocks of 256 (two panels of 128
    each on the diagonal, the least a panel can be: two blocks of the
    mask's 128 rows in a half's block); the backward is the ONE
    streamed kernel either way (ISSUE 39), since the rows are never one
    block."""
    d = 128
    if block:
        monkeypatch.setattr(FA, "_TILE", block // 2)
    assert FA._tile(block or seq, FA._TILE) * (2 if block else 1) \
        == (block or seq)
    q, k, v, dy, _ = _gqa_inputs(h, hkv, d, 2 * seq, dtype, seed=3)
    kw = dict(causal=True, force="interpret", block_q=block, block_k=block,
              n_kv_head=hkv, mask_block=mask_block, own_block=True)
    dense = lambda q, k, v: _dense_block_causal(
        q, k, v, h, hkv, mask_block, False, own=True)[0]
    run = lambda q, k, v: FA.flash_bthd(q, k, v, h, **kw)
    tol = 5e-3 if dtype == jnp.float32 else 2e-2
    weigh = lambda o: (_f32(o) * _f32(dy)).sum()
    # out and the gradients: the kernels' as one program traced once,
    # and the dense form's as one
    eqns, (o, got) = _traced_once(_with_grads(run, weigh), q, k, v)
    assert [eqn.params["name"] for eqn in eqns] == ["flash_fwd", "flash_bwd"]
    assert FA._backward_for(q, h, FA._mask_of(True, mask_block, False, True)[0],
                            block or seq, block or seq) == "fused_streamed"
    o_ref, want = jax.jit(_with_grads(dense, weigh))(*_host32(q, k, v))
    assert o.shape == q.shape and o.dtype == dtype
    _assert_close("out", o, o_ref, tol)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == dtype
        for half, rows in (("noised", slice(0, seq)),
                           ("clean", slice(seq, None))):
            _assert_close(name + " " + half, _np32(a)[:, rows],
                          _np32(b)[:, rows], tol)


def test_own_blocks_first_noised_rows_see_themselves_alone():
    """The first block's noised rows see no clean key (a strict call
    gave them lse -1e30 for the merge to weigh out): inside the kernels
    their own block is all their softmax runs over, so their output is
    the attention of q's first rows on the noised keys of those rows,
    whatever the clean half holds."""
    h, hkv, d, seq, m = 4, 2, 128, 256, 4
    q, k, v, _, _ = _gqa_inputs(h, hkv, d, 2 * seq, jnp.float32, seed=5)
    run = lambda k, v: FA.flash_bthd(
        q, k, v, h, causal=True, force="interpret", n_kv_head=hkv,
        mask_block=m, own_block=True, block_q=128, block_k=128)
    o = run(k, v)
    alone = _dense_block_causal(q[:, :m], k[:, :m], v[:, :m], h, hkv, m,
                                False)[0]
    _assert_close("first block", o[:, :m], alone, 1e-5)
    other = run(k.at[:, seq:].multiply(-3.0), v.at[:, seq:].add(1.0))
    assert bool((other[:, :m] == o[:, :m]).all())
    assert not bool((other[:, m:2 * m] == o[:, m:2 * m]).all())


def test_own_block_form_counts_itself_and_goes_dense_where_it_must():
    """The counter's `mask` label reads `block_causal_own`, the backward
    `fused_streamed` also where a half is one block; what the kernels
    cannot take (unequal blocks, two heads of 64 to a block in groups of
    three) is the dense mask; `strict`, a full mask or an odd count of rows with
    the form is a ValueError."""
    count = FA._LOWERINGS
    q, k, v, _, _ = _gqa_inputs(4, 2, 128, 512, jnp.float32)
    labels = dict(path="interpret", entry="bthd", heads_per_block="1",
                  backward="fused_streamed", mask="block_causal_own",
                  kv_groups="2", key_width="128", value_width="128",
                  second_part="none", window="0")
    was = count.value(**labels)
    kw = dict(causal=True, force="interpret", n_kv_head=2, mask_block=4,
              own_block=True)
    o = FA.flash_bthd(q, k, v, 4, **kw)
    assert count.value(**labels) == was + 1
    labels.update(path="dense", backward="none")
    was = count.value(**labels)
    o_dense = FA.flash_bthd(q, k, v, 4, block_q=128, block_k=256, **kw)
    assert count.value(**labels) == was + 1
    want = _dense_block_causal(q, k, v, 4, 2, 4, False, own=True)[0]
    _assert_close("kernels", o, want, 1e-5)
    _assert_close("dense", o_dense, want, 1e-5)
    # (groups of 3: two heads of 64 to a block do not divide one)
    q, k, v, _, _ = _gqa_inputs(6, 2, 64, 512, jnp.float32)
    labels.update(heads_per_block="2", key_width="64", value_width="64",
                  kv_groups="3")
    was = count.value(**labels)
    FA.flash_bthd(q, k, v, 6, **kw)
    assert count.value(**labels) == was + 1
    q, k, v, _, _ = _gqa_inputs(4, 2, 64, 512, jnp.float32)
    for bad in (dict(strict=True), dict(causal=False)):
        with pytest.raises(ValueError):
            FA.flash_bthd(q, k, v, 4, **dict(kw, **bad))
    with pytest.raises(ValueError):
        FA.flash_bthd(q[:, :255], k[:, :255], v[:, :255], 4, **kw)
    assert "block_causal_own" in count.help
