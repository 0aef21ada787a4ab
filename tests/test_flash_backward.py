"""The flash kernels' ONE backward kernel where a block holds all of T
(ops/flash_attention.py, PR 31), in interpret mode: against dense
float32 math and, bit for bit, against what PR 32's parent gave (hashes
of inputs at T 512: that case keeps its shape). One file of the kernel
family's seven (tests/flash_test.py holds what they share); the
streamed backwards are tests/test_flash_backward_streamed.py's."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import flash_attention as FA
from flash_test import (_assert_close, _bthd_inputs, _dense_lse, _f32,
                        _gqa_inputs, _grads_of, _host32, _kernels_and_grads,
                        _pallas_names)


# -- one backward kernel where a block holds all of T (PR 31) -----------------

@pytest.mark.parametrize("h, hkv, d, g", [
    pytest.param(4, 4, 64, 2, id="H4-D64-g2"),
    pytest.param(2, 2, 128, 1, id="H2-D128-g1"),
    pytest.param(3, 3, 64, 3, id="H3-D64-all_of_H"),
    pytest.param(8, 2, 64, 2, id="H8-Hkv2-D64-g2-groups_of_4")])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("with_dlse", [False, True], ids=["out", "out_lse"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_backward_matches_dense(monkeypatch, dtype, with_dlse, causal,
                                      h, hkv, d, g):
    """dq, dk, dv of the ONE backward kernel (all of T 256 in a block,
    two panels of 128 keys, the fewest that have dq accumulated across
    them in scratch) against dense float32 math, with and without an
    lse cotangent folded into the delta the kernel makes and keeps; two
    heads of 64 to a block under groups of 4 (ISSUE 49: k and v spread
    under the query heads' lanes, dk and dv folded back over a group
    in k's own shape)."""
    t = 256
    monkeypatch.setattr(FA, "_TILE", 128)
    monkeypatch.setattr(FA, "_PANEL_SCORES", 128 * t)
    assert FA.heads_per_block(h, d) == g
    assert t // FA._tile(t, FA._TILE) == 2
    assert FA._backward_of(t, g * d, t, t,
                           itemsize=jnp.dtype(dtype).itemsize) == "fused"
    q, k, v, dy, dlse = _bthd_inputs(h, d, dtype, t=t, seed=8) \
        if hkv == h else _gqa_inputs(h, hkv, d, t, dtype, seed=8)
    scale = d ** -0.5

    def loss(att):
        def f(q, k, v):
            o, lse = att(q, k, v)
            extra = (lse * dlse).sum() if with_dlse else 0.0
            return (_f32(o) * _f32(dy)).sum() + extra
        return f

    def ref(q, k, v):
        o, lse = _dense_lse(FA.heads_first(q, h), FA.heads_first(k, hkv),
                            FA.heads_first(v, hkv), causal, scale)
        return FA.heads_last(o), lse

    def got(q, k, v):
        kw = dict(causal=causal, force="interpret", n_kv_head=hkv)
        if with_dlse:
            return FA.flash_bthd_lse(q, k, v, h, **kw)
        return FA.flash_bthd(q, k, v, h, **kw), None

    names, grads = _kernels_and_grads(loss(got), q, k, v)
    assert names == ["flash_fwd", "flash_bwd"]
    g_ref = _grads_of(loss(ref), *_host32(q, k, v))
    for name, a, b, x in zip(("dq", "dk", "dv"), grads, g_ref, (q, k, v)):
        assert a.shape == x.shape and a.dtype == dtype
        _assert_close(name, a, b, 5e-3 if dtype == jnp.float32 else 2e-2)


# sha256 (first 16 hex digits) of dq, dk, dv as float32 bytes from the
# ONE-block causal backward (flash_bwd, two heads of 64 to a block: the
# path the benchmark's OPT cell takes) at commit a539599, PR 32's parent,
# before the kernels learnt a mask and a group size.
_PARENT_FUSED = {
    ("float32", False): ("1848e6b5aed696ee", "f7afa5d50d2ee3ab", "c03bbf9fcf6e3b7b"),
    ("float32", True): ("5e96e14ece489662", "1a733a770f92ac14", "c03bbf9fcf6e3b7b"),
    ("bfloat16", False): ("7d7c2e4e9466d224", "b311016cf14f5045", "af90e6f377929259"),
    ("bfloat16", True): ("9bb9de23b235f79a", "21cba15f2d22ecae", "af90e6f377929259"),
}


@pytest.mark.parametrize("with_dlse", [False, True], ids=["out", "out_lse"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_causal_backward_is_the_parents_bit_for_bit(dtype, with_dlse):
    """The causal path of the OPT cell (g 2, one block, flash_bwd)
    lowers to the kernels it had: every bit of dq, dk, dv is what PR
    32's parent gave at T 512."""
    import hashlib
    h, d = 4, 64
    q, k, v, dy, dlse = _bthd_inputs(h, d, dtype, t=512, b=2, seed=9)

    def f(q, k, v):
        kw = dict(causal=True, force="interpret")
        if not with_dlse:
            return (_f32(FA.flash_bthd(q, k, v, h, **kw)) * _f32(dy)).sum()
        o, lse = FA.flash_bthd_lse(q, k, v, h, **kw)
        return (_f32(o) * _f32(dy)).sum() + (lse * dlse).sum()

    grad = jax.grad(f, (0, 1, 2))
    assert _pallas_names(jax.make_jaxpr(grad)(q, k, v).jaxpr) \
        == ["flash_fwd", "flash_bwd"]
    got = tuple(hashlib.sha256(np.asarray(_f32(g)).tobytes()
                               ).hexdigest()[:16] for g in grad(q, k, v))
    case = (jnp.dtype(dtype).name, with_dlse)
    assert got == _PARENT_FUSED[case], (case, got)
