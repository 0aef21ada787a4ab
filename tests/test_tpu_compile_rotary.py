"""The QK-norm + RoPE kernel pair of ``ops/rotary.py`` compiled for a
described v5e (tests/tpu_compile_test.py says how and why), at the
block-diffusion cell's shape and at `lfm2_train_T32k`'s heads of 64.
"""

import functools

import pytest
from tpu_compile_test import _compiled_text, chip, topo  # noqa: F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops.flash_attention import flash_bthd  # noqa: E402


@pytest.mark.parametrize("h, hkv, d, wrap, mask_block", [
    (32, 4, 128, 2048, 4), (32, 8, 64, 0, 1)],
    ids=["heads_of_128", "heads_of_64"])
def test_nothing_relays_q_or_k_between_a_projection_and_the_kernels(
        chip, h, hkv, d, wrap, mask_block):
    """The block-diffusion cell's attention up to its kernels (ISSUE
    33): projections, QK-norm and RoPE as ONE op each for q and k, then
    the flash kernels, compiled for the v5e. Forward: two
    `qk_norm_rope_fwd` calls, and between `mul` and `flash_fwd` no
    `reshape`, `concatenate`, `pad`, `slice`, `copy` or `transpose`
    result of q's or k's size: both stay [B, T, H*D] bfloat16 as the
    projections' matmuls write them. Backward: two `qk_norm_rope_bwd`
    more, no such result of q's size, and of k's size only what the same
    layer with neither norm nor rotation has: the `pad`s that put the
    key/value heads' group sums of dk and dv side by side (since ISSUE
    37 the sums are of lane slices, fused into one pass: the copy of
    the kernels' float32 dk and dv into another tiling is gone). Each
    op alone (`rms_norm` grouped, `rope`) lowers to the same kernels.
    Heads of 64 (ISSUE 50, `lfm2_train_T32k`'s 32 and 8, no mask) sit two
    to a lane tile in the same kernels: Mosaic takes the tile's body,
    `broadcast` joins the moves, and none is made beyond the bare
    layer's own (there the flash kernels' callers lay the key/value
    heads out per query head, with or without the norm and rotation)."""
    import collections
    import math
    import re
    from paddle_tpu.ops import rotary
    b, t, dm = 2, 4096, 2048
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=chip)
    scale = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=chip)
    avals = (sds(b, t, dm), sds(dm, h * d), sds(dm, hkv * d),
             sds(dm, hkv * d), scale, scale)
    moves = ("reshape", "concatenate", "pad", "slice", "copy", "transpose")
    if d < 128:
        moves += ("broadcast",)

    def layer(fused, x, wq, wk, wv, sq, sk):
        if fused is None:
            turn = lambda y, s, n: y
        elif fused:
            turn = lambda y, s, n: rotary.norm_rope(y, s, n, 1e6, wrap,
                                                    1e-6, force="pallas")
        else:
            turn = lambda y, s, n: rotary.norm_rope(
                rotary.norm_rope(y, s, n, force="pallas"), None, n, 1e6,
                wrap, force="pallas")
        return flash_bthd(turn(x @ wq, sq, h), turn(x @ wk, sk, hkv), x @ wv,
                          h, causal=True, force="pallas", n_kv_head=hkv,
                          mask_block=mask_block)

    def sized(text):
        """(op, size) of every result of q's or k's size."""
        found = []
        for line in text.split("\n"):
            m = re.search(r"= (?:bf16|f32)\[([\d,]+)\]\{[^}]*\} ([\w-]+)\(",
                          line)
            size = m and math.prod(int(n) for n in m.group(1).split(","))
            if size in (b * t * h * d, b * t * hkv * d):
                found.append((m.group(2), size))
        return found

    calls = lambda text, name: len(re.findall(r"%%%s[.\d]* = " % name, text))
    moved = lambda text, size=0: [
        op for op, n in sized(text) if op in moves and n >= size]
    grad = lambda fused: jax.grad(
        lambda *a: layer(fused, *a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3))
    beyond = lambda text, bare: (collections.Counter(moved(text))
                                 - collections.Counter(bare))
    bare_forward = moved(_compiled_text(functools.partial(layer, None),
                                        *avals))
    assert d < 128 or not bare_forward
    bare = moved(_compiled_text(grad(None), *avals))
    for fused, n in ((True, 2), (False, 4)):
        text = _compiled_text(functools.partial(layer, fused), *avals)
        assert calls(text, "qk_norm_rope_fwd") == n
        assert text.count("tpu_custom_call") == n + 1
        assert len(sized(text)) >= n and not beyond(text, bare_forward)
        text = _compiled_text(grad(fused), *avals)
        assert calls(text, "qk_norm_rope_fwd") == n
        assert calls(text, "qk_norm_rope_bwd") == n
        # the flash forward and, since ISSUE 39, ONE flash backward
        assert text.count("tpu_custom_call") == 2 * n + 2
        assert not beyond(text, bare)
        if d == 128:
            assert not moved(text, b * t * h * d)
            assert set(moved(text)) == {"pad"} == set(bare)
            assert len(moved(text)) <= len(bare)
