"""Differential attention (arXiv:2410.05258) as the SambaY decoder
carries it (ISSUE 40): two Program ops.

``diff_attention`` is the two softmaxes: q [B, T, H*D] against k and v
[B, T, Hkv*D], causal, under `window` keys where the layer has one,
through the streamed flash kernels (``flash_attention.flash_diff_bthd``,
which says how a head of 64 with a value of 128 is laid out for them).
A1 and A2 [B, T, (H/2)*2D]: every differential head's first and second
softmax times its value. The kernels keep the op's scope in a device
trace and under it a second one, the attr `kind`: ``window``, ``full``
or ``cross`` (a cross layer's k and v are those another layer made; to
the kernels it is a full one).

``diff_attn`` is what joins them: ``lam = exp(lq1 . lk1) - exp(lq2 .
lk2) + lam0``, ``o = RMSNorm_2D(a1 - lam a2) * (1 - lam0)``, float32
inside, one learned weight [2D] for every head.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register
from .flash_attention import flash_diff_bthd


def diff_combine(a1, a2, lq1, lk1, lq2, lk2, weight, lam0, epsilon):
    """a1, a2 [B, T, P*W] -> [B, T, P*W], the RMSNorm over each head's
    W lanes. The mean of squares over a head is a product with the
    heads' indicator [P*W, P] and goes back over the lanes by its
    transpose: [B, T, P*W] is never reshaped to [.., P, W], which on
    the chip is a copy into another tiling (in the cell's first trace
    that form took 3.3 ms a pass; my chip run, PR 40)."""
    f32 = jnp.float32
    w, lanes = weight.shape[0], a1.shape[-1]
    heads = jnp.asarray(np.arange(lanes)[:, None] // w
                        == np.arange(lanes // w)[None, :], f32)
    dot = lambda x, y: jnp.sum(x.astype(f32) * y.astype(f32))
    over = lambda x, y: jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)
    lam = jnp.exp(dot(lq1, lk1)) - jnp.exp(dot(lq2, lk2)) + lam0
    o = a1.astype(f32) - lam * a2.astype(f32)
    inv = jax.lax.rsqrt(over(o * o, heads) / w + epsilon)
    o = o * over(inv, heads.T) * jnp.tile(weight.astype(f32), lanes // w)
    return (o * (1.0 - lam0)).astype(a1.dtype)


@register("diff_attention")
def _diff_attention(ctx, op):
    """Q [B, T, H*D], K and V [B, T, Hkv*D]; attrs n_head, n_kv_head,
    window (0: none), kind. A1, A2 [B, T, (H/2)*2D]."""
    with jax.named_scope(op.attr("kind", "full")):
        a1, a2 = flash_diff_bthd(
            ctx.in1(op, "Q"), ctx.in1(op, "K"), ctx.in1(op, "V"),
            int(op.attr("n_head")), int(op.attr("n_kv_head")),
            int(op.attr("window", 0)) or None)
    ctx.set_out(op, "A1", a1)
    ctx.set_out(op, "A2", a2)


@register("diff_attn")
def _diff_attn(ctx, op):
    """A1, A2 [B, T, P*W], LambdaQ1 / K1 / Q2 / K2 [D], Scale [W]; attrs
    lambda_init, epsilon. Out [B, T, P*W]."""
    ctx.set_out(op, "Out", diff_combine(
        ctx.in1(op, "A1"), ctx.in1(op, "A2"), ctx.in1(op, "LambdaQ1"),
        ctx.in1(op, "LambdaK1"), ctx.in1(op, "LambdaQ2"),
        ctx.in1(op, "LambdaK2"), ctx.in1(op, "Scale"),
        float(op.attr("lambda_init")), float(op.attr("epsilon", 1e-5))))
