"""Causal attention over grouped heads with an optional window bound
(ISSUE 38): the score and value of a layer whose stack mixes
sliding-window and full attention.

The Program op `causal_attention` takes the projections as they come, q
[B, T, H*D] and k, v [B, T, Hkv*D] (query head h reads key/value head
h // (H / Hkv)), and hands them to the flash kernels: plain causal, or
under `window` w the keys `i - w < j <= i` alone, whose blocks under
the band the kernels do not walk (`ops/flash_attention.py`, "a window
bound"). QK-norm and the rotary embedding are the op `qk_norm_rope`
before it (rotation off where a layer carries no position signal), the
output gate the op `sigmoid_mul` after it. The kernels keep the op's
scope in a device trace, and under it a second one, `window` or `full`,
so that a reader tells a window layer's kernels from a full layer's.
"""

import jax

from ..core.registry import register
from .flash_attention import flash_bthd


def causal_attention(q, k, v, n_head, n_kv_head, window=0, scale=None,
                     force=None):
    """softmax(q k^T * scale, causal [and within `window` keys]) v over
    [B, T, .] operands: [B, T, H*D]."""
    with jax.named_scope("window" if window else "full"):
        return flash_bthd(q, k, v, n_head, causal=True, scale=scale,
                          force=force, n_kv_head=n_kv_head,
                          window=window or None)


@register("causal_attention")
def _causal_attention(ctx, op):
    """Q [B, T, H*D], K and V [B, T, Hkv*D]; attrs n_head, n_kv_head,
    window (0: none), scale (0: D^-0.5). Out [B, T, H*D]."""
    ctx.set_out(op, "Out", causal_attention(
        ctx.in1(op, "Q"), ctx.in1(op, "K"), ctx.in1(op, "V"),
        int(op.attr("n_head")), int(op.attr("n_kv_head")),
        int(op.attr("window", 0)), float(op.attr("scale", 0.0)) or None))
