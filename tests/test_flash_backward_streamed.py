"""The flash kernels' streamed backwards (ops/flash_attention.py), in
interpret mode: the ONE streamed kernel that holds dq in VMEM (ISSUE
39) and the two kernels beyond its byte bound, against dense float32
math and, bit for bit, against what PR 31's parent gave (hashes of
inputs at T 512: those cases keep their shapes). One file of the kernel
family's seven (tests/flash_test.py holds what they share); the one
kernel of a T in one block is tests/test_flash_backward.py's."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import flash_attention as FA
from flash_test import (  # noqa: F401  (two_kernels: a fixture)
    _assert_close, _bthd_inputs, _dense_block_causal, _dense_lse, _f32,
    _gqa_inputs, _kernels_and_grads, _np32, _pallas_names, two_kernels)


# sha256 (first 16 hex digits) of dq, dk, dv as float32 bytes, from
# `_streamed_grads` run at commit 88444d7, PR 31's parent, whose
# flash_bwd_dq / flash_bwd_dkv still had their one-block branches. The
# kernels run in interpret mode: XLA's CPU dots, the same on a machine
# whatever the tree.
_PARENT_STREAMED = {
    ("float32", False): ("36def30fa06eecf5", "2fece4931d993ef2", "8d4fe080df03d3c1"),
    ("float32", True): ("42089e908b2c1580", "f74aa46134dec4ec", "8d4fe080df03d3c1"),
    ("bfloat16", False): ("ff8e2ad18a5bdfe6", "0eca2ce7910b981a", "a808b4560d51ddbc"),
    ("bfloat16", True): ("e37a800128f83e9e", "78c549a2470053da", "a808b4560d51ddbc"),
}


def _streamed_grads(dtype, with_dlse):
    """(the gradient function, its arguments) at T 512 in 2 x 2 major
    blocks of 256, two heads of 64 to a block, causal."""
    h, d = 4, 64
    q, k, v, dy, dlse = _bthd_inputs(h, d, dtype, t=512, b=2, seed=9)

    def f(q, k, v):
        kw = dict(causal=True, force="interpret", block_q=256, block_k=256)
        if not with_dlse:
            return (_f32(FA.flash_bthd(q, k, v, h, **kw)) * _f32(dy)).sum()
        o, lse = FA.flash_bthd_lse(q, k, v, h, **kw)
        return (_f32(o) * _f32(dy)).sum() + (lse * dlse).sum()

    return jax.grad(f, (0, 1, 2)), (q, k, v)


@pytest.mark.parametrize("with_dlse", [False, True], ids=["out", "out_lse"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_streamed_backward_is_the_parents_bit_for_bit(two_kernels, dtype,
                                                      with_dlse):
    """Several blocks a sequence, over the byte bound of the one
    streamed kernel: the backward is still flash_bwd_dq then
    flash_bwd_dkv, and every bit of dq, dk, dv is what PR 31's parent
    gave on these inputs."""
    import hashlib
    grad, args = _streamed_grads(dtype, with_dlse)
    assert _pallas_names(jax.make_jaxpr(grad)(*args).jaxpr) \
        == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    got = tuple(hashlib.sha256(np.asarray(_f32(g)).tobytes()
                               ).hexdigest()[:16] for g in grad(*args))
    case = (jnp.dtype(dtype).name, with_dlse)
    assert got == _PARENT_STREAMED[case], (case, got)


@pytest.mark.parametrize("with_dlse", [False, True], ids=["out", "out_lse"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_one_streamed_kernel_is_the_two_kernels_sums(monkeypatch, dtype,
                                                     with_dlse):
    """The same inputs through the ONE streamed kernel (ISSUE 39): the
    forward and flash_bwd, and dq, dk, dv the two kernels' sums in
    another order: float32's rounding apart in float32, a bf16 step of
    the largest value in bf16 (each gradient is rounded once, at its
    store)."""
    grad, args = _streamed_grads(dtype, with_dlse)
    assert _pallas_names(jax.make_jaxpr(grad)(*args).jaxpr) \
        == ["flash_fwd", "flash_bwd"]
    got = grad(*args)
    monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)
    for name, a, b in zip(("dq", "dk", "dv"), got, grad(*args)):
        assert a.dtype == dtype
        _assert_close(name, a, _f32(b), 1e-6 if dtype == jnp.float32
                      else 8e-3)


# the forms the ONE streamed kernel walks, as flash_bthd's arguments
# beside (H, Hkv, D, T, block_q, block_k): T in several major blocks. A
# block of one panel is 64 rows (4 x 4 blocks at T 256: a q block's
# first, middle and last visits, blocks below, on and above the
# diagonal); the forms that name panels or unequal counts keep blocks
# of 128-row panels (_TILE is 128 here)
_STREAMED = [
    pytest.param(2, 2, 128, 256, 64, 64, dict(causal=False), id="full"),
    pytest.param(2, 2, 128, 256, 64, 64, dict(causal=True), id="causal"),
    pytest.param(4, 4, 64, 256, 64, 64, dict(causal=True),
                 id="causal-two_heads_of_64_to_a_block"),
    pytest.param(3, 3, 64, 512, 256, 256, dict(causal=True),
                 id="causal-all_of_H_192_lanes"),
    pytest.param(8, 2, 64, 256, 64, 64, dict(causal=True),
                 id="causal-two_heads_of_64_to_a_block-groups_of_4"),
    pytest.param(2, 1, 128, 768, 256, 128, dict(causal=True),
                 id="causal-nq3_nk6"),
    pytest.param(2, 1, 128, 768, 128, 384, dict(causal=True),
                 id="causal-nq6_nk2"),
    pytest.param(2, 1, 128, 256, 256, 64, dict(causal=False),
                 id="full-nq1_nk4"),
    pytest.param(8, 1, 128, 256, 64, 64, dict(causal=True, mask_block=32),
                 id="block_causal-group8"),
    pytest.param(8, 1, 128, 256, 64, 64,
                 dict(causal=True, mask_block=4, strict=True),
                 id="block_causal_strict-group8"),
    pytest.param(8, 1, 128, 256, 64, 64,
                 dict(causal=True, mask_block=4, own_block=True),
                 id="own_block-group8"),
    pytest.param(8, 1, 128, 1024, 256, 256,
                 dict(causal=True, mask_block=128, own_block=True),
                 id="own_block-panels-group8"),
]


@functools.lru_cache(maxsize=None)
def _dense_side(dtype, h, hkv, d, t, form):
    """((q, k, v), the loss of an (out, lse) pair, dense float32
    gradients of it with the mask written out) for one of _STREAMED's
    forms, its items as a tuple: ONE compiled program, made once for
    both backwards."""
    form = dict(form)
    own = form.get("own_block", False)
    mask_block, strict = form.get("mask_block", 1), form.get("strict", False)
    q, k, v, dy, dlse = _gqa_inputs(h, hkv, d, t, dtype, seed=17)

    def dense(q, k, v):
        if not form["causal"]:
            o, lse = _dense_lse(
                FA.heads_first(q, h), FA.heads_first(k, hkv),
                FA.heads_first(v, hkv), False, d ** -0.5)
            return FA.heads_last(o), lse, jnp.ones((t,), bool)
        return _dense_block_causal(q, k, v, h, hkv, mask_block, strict, own)

    def weigh(o, lse, seen):
        out = (_f32(o) * _f32(dy)
               * seen.astype(jnp.float32)[None, :, None]).sum()
        return out if own else out + (
            jnp.where(seen, lse, 0.0) * dlse).sum()

    def of_dense(q, k, v):
        o, lse, seen = dense(q, k, v)
        return weigh(o, lse, seen), seen

    want, seen = jax.jit(jax.grad(of_dense, (0, 1, 2), has_aux=True))(
        *(jnp.asarray(_np32(x)) for x in (q, k, v)))
    return (q, k, v), lambda o, lse: weigh(o, lse, seen), want


@pytest.mark.parametrize("h, hkv, d, t, bq, bk, form", _STREAMED)
@pytest.mark.parametrize("backward", ["fused_streamed", "two_kernels"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_streamed_backward_matches_dense(monkeypatch, dtype, backward, h,
                                         hkv, d, t, bq, bk, form):
    """dq, dk, dv of a streamed T against dense float32 math with the
    mask written out, through the ONE kernel (dq for all rows in VMEM
    scratch across the key blocks, delta made at a q block's first
    visit) and, the byte bound set to nothing, through the two kernels
    it replaced: every mask form, grouped heads 8:1, two heads to a
    block (alone and, ISSUE 49, in groups of 4 reading one key/value
    head) and all of H, unequal counts of q and key blocks, and an lse
    cotangent where the form gives an lse."""
    monkeypatch.setattr(FA, "_TILE", 128)
    if backward == "two_kernels":
        monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)
    own = form.get("own_block", False)
    (q, k, v), loss, want = _dense_side(dtype, h, hkv, d, t,
                                        tuple(sorted(form.items())))
    kw = dict(force="interpret", block_q=bq, block_k=bk, n_kv_head=hkv,
              **form)

    def run(q, k, v):
        if own:                 # the form gives no lse
            return FA.flash_bthd(q, k, v, h, **kw), None
        return FA.flash_bthd_lse(q, k, v, h, **kw)

    names, got = _kernels_and_grads(lambda *a: loss(*run(*a)), q, k, v)
    assert names == ["flash_fwd"] + {
        "fused_streamed": ["flash_bwd"],
        "two_kernels": ["flash_bwd_dq", "flash_bwd_dkv"]}[backward]
    # streamed: the rows the blocks are cut from are several of them
    mask = FA._mask_of(form["causal"], form.get("mask_block", 1),
                       form.get("strict", False), own)[0]
    assert FA._backward_for(q, h, mask, bq, bk) == backward
    tol = 5e-3 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == dtype
        assert np.isfinite(_np32(a)).all(), name
        _assert_close(name, a, b, tol)
