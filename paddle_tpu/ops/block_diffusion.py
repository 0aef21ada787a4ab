"""Block-diffusion training (SDAR / BD3-LM, ISSUE 32): the noise and
the attention over ``[noised; clean]`` rows.

A sequence ``x_0`` of L tokens is cut into blocks of ``block`` tokens.
Each block draws a noise level t and each of its tokens becomes the
mask id with probability t. The layers run on 2L rows a sequence,
``[x_t; x_0]``, both halves at positions 0..L-1, under this mask, with
``B(i) = i // block``:

* a noised query i sees noised key j iff ``B(j) == B(i)`` and clean key
  j iff ``B(j) < B(i)``;
* a clean query i sees clean key j iff ``B(j) <= B(i)``, and no noised
  key.

Of the (2L)^2 scores L^2 + L * block are useful. ``attention`` computes
them in ONE call of each flash kernel under the kernels' own-block mask
form (``flash_bthd(..., own_block=True)``, ISSUE 37): both halves walk
the clean keys as block-causal attention does, and a noised row's tile
on the diagonal takes the noised keys of its own block as one segment
more of the same streaming-softmax step. No [T, T] tensor reaches HBM,
no wholly masked tile is computed, and nothing of attention runs outside
the kernels but the sums of dk and dv over a group of query heads.

The noise is a pure function of explicit integers (a salt, the step,
the batch row; ``draw_noise``), not of the executor's per-op key, so
that a reference can make it again.
"""

import jax
import jax.numpy as jnp

from ..core.registry import register
from .flash_attention import flash_bthd

T_MIN = 1e-3


def draw_noise(salt, step, batch, seq_len, block):
    """(masked [batch, seq_len] bool, t [batch, seq_len] float32): for
    batch row b a key folded from `salt`, `step` and b; from it t ~
    U(T_MIN, 1) a block and a uniform a token, masked where under its
    block's t."""
    def row(b):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(salt), step), b)
        k_t, k_u = jax.random.split(key)
        t = jax.random.uniform(k_t, (seq_len // block,), jnp.float32,
                               T_MIN, 1.0)
        t = jnp.repeat(t, block)
        return jax.random.uniform(k_u, (seq_len,), jnp.float32) < t, t
    return jax.vmap(row)(jnp.arange(batch))


@register("block_diffusion_noise")
def _block_diffusion_noise(ctx, op):
    """X [B, L] tokens, Salt and Step [1] int32 (persistable) -> Noised
    [B, L] (the mask id where masked), Weight [B, L] float32 (1/t where
    masked, else 0: the loss's weight) and StepOut = Step + 1. A
    `for_test` clone reads Step and leaves it: the forward it runs for
    a comparison draws what the next train step draws. The salt is a
    variable and not an attribute, so that programs of different seeds
    are one executable."""
    x = ctx.in1(op, "X")
    step = ctx.in1(op, "Step")
    masked, t = draw_noise(ctx.in1(op, "Salt").reshape(()),
                           step.reshape(()), x.shape[0], x.shape[1],
                           int(op.attr("block")))
    ctx.set_out(op, "Noised", jnp.where(
        masked, jnp.asarray(op.attr("mask_id"), x.dtype), x))
    ctx.set_out(op, "Weight", jnp.where(masked, 1.0 / t, 0.0))
    if not (op.attr("is_test", False) or ctx.is_test):
        ctx.set_out(op, "StepOut", step + 1)


def attention(q, k, v, n_head, n_kv_head, block, scale=None, force=None):
    """q [B, 2L, H*D], k and v [B, 2L, Hkv*D], rows [noised; clean] ->
    [B, 2L, H*D] under the block-diffusion mask (the module's
    docstring): the flash kernels' own-block form, on the arrays as the
    projections wrote them."""
    return flash_bthd(q, k, v, n_head, causal=True, scale=scale,
                      force=force, n_kv_head=n_kv_head, mask_block=block,
                      own_block=True)


@register("block_diffusion_attention")
def _block_diffusion_attention(ctx, op):
    q, k, v = (ctx.in1(op, s) for s in ("Q", "K", "V"))
    ctx.set_out(op, "Out", attention(
        q, k, v, int(op.attr("n_head")), int(op.attr("n_kv_head")),
        int(op.attr("block")), float(op.attr("scale", 0.0)) or None))
