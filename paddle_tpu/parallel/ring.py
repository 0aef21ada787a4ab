"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Long-context design (task requirement; beyond the 2018 reference, which
handled long sequences only by LoD batching — SURVEY.md §5.7): the sequence
axis is sharded across devices; each device holds a Q shard and passes its
K/V shard around the ring with ``ppermute`` while merging
flash-attention-style partial results (per-shard output + log-sum-exp
rows), so the full [T, T] score matrix never materializes and K/V
transfers overlap with the blockwise matmuls (Liu et al., Ring Attention
with Blockwise Transformers).

Each ring step computes attention of the local Q shard against the
currently-held K/V shard with the fused Pallas flash kernel
(ops/flash_attention.flash_attention_lse — dense math off-TPU), then
merges (out_i, lse_i) into the running accumulator by stable
log-sum-exp weighting. Because shards are contiguous sequence chunks,
the causal mask per step collapses to three cases: the diagonal shard is
plain causal attention, earlier shards are unmasked, later shards
contribute nothing.

Layout: everything here is [B, H, T, D], the sequence axis sharded. The
flash kernels read [B, T, H*D] since PR 29; `flash_attention` /
`flash_attention_lse` transpose into that and back, so each ring step
pays two transposes the kernels' own entry (`flash_bthd`) does not. No
benchmark cell runs ring or Ulysses attention.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

_NEG_BIG = -1e30   # finite "-inf": keeps exp()==0 without inf-inf NaNs


def _ring_attention_sharded(q, k, v, axis_name, causal, scale):
    """Per-shard body (inside shard_map). q/k/v: [B, H, T_local, D]."""
    from ..ops.flash_attention import flash_attention_lse

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, t_local, d = q.shape

    def diag_block(k_cur, v_cur):      # src == me: aligned causal mask
        return flash_attention_lse(q, k_cur, v_cur, causal=causal,
                                   scale=scale)

    def full_block(k_cur, v_cur):      # src strictly before me: no mask
        return flash_attention_lse(q, k_cur, v_cur, causal=False,
                                   scale=scale)

    def skip_block(k_cur, v_cur):      # src after me: fully masked out
        return (jnp.zeros(q.shape, q.dtype),
                jnp.full((b, h, t_local), _NEG_BIG, jnp.float32))

    # Deferred-normalization carry (one divide AFTER the loop, not per
    # step): num = Σ_seen o_j·e^{lse_j - m_run}, s = Σ_seen e^{lse_j -
    # m_run}, with m_run the running max of the seen shards' lse rows.
    num = jnp.zeros((b, h, t_local, d), jnp.float32)
    s = jnp.zeros((b, h, t_local), jnp.float32)
    m_run = jnp.full((b, h, t_local), _NEG_BIG, jnp.float32)

    def ring_step(i, carry):
        num, s, m_run, k_cur, v_cur = carry
        src_idx = (my_idx - i) % axis_size   # whose K/V shard we hold now
        if causal:
            case = jnp.where(src_idx == my_idx, 0,
                             jnp.where(src_idx < my_idx, 1, 2))
            o_i, lse_i = lax.switch(case, (diag_block, full_block,
                                           skip_block), k_cur, v_cur)
        else:
            o_i, lse_i = full_block(k_cur, v_cur)
        m_new = jnp.maximum(m_run, lse_i)
        alpha = jnp.exp(m_run - m_new)       # rescales the old partials
        w_i = jnp.exp(lse_i - m_new)         # this shard's weight
        num = num * alpha[..., None] \
            + o_i.astype(jnp.float32) * w_i[..., None]
        s = s * alpha + w_i
        # rotate K/V shards around the ring (overlaps with the next
        # step's matmuls after XLA latency-hiding scheduling)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return num, s, m_new, k_nxt, v_nxt

    num, s, m_run, _, _ = lax.fori_loop(0, axis_size, ring_step,
                                        (num, s, m_run, k, v))
    return (num / jnp.maximum(s, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name="sp", causal=False, scale=None,
                   batch_axis=None):
    """q,k,v: [B, H, T, D] with T sharded on `axis_name`. Returns [B,H,T,D]
    with the same sharding. Pass batch_axis="dp" when the mesh also data-
    parallelizes the batch dim, so shard_map doesn't gather it."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P(batch_axis, None, axis_name, None)
    fn = shard_map(
        functools.partial(_ring_attention_sharded, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def ulysses_attention(q, k, v, mesh, axis_name="sp", causal=False,
                      scale=None, batch_axis=None):
    """DeepSpeed-Ulysses style sequence parallelism: all-to-all swaps the
    sharded axis from sequence to heads, runs full local attention (the
    fused flash kernel on TPU), then swaps back. Better when H >=
    axis_size and T is moderate."""
    from ..ops.flash_attention import flash_attention

    if scale is None:
        scale = q.shape[-1] ** -0.5

    def body(q, k, v):
        # local shards [B, H, T/s, D] → a2a → [B, H/s, T, D]
        def a2a(x, split, concat):
            return lax.all_to_all(x, axis_name, split_axis=split,
                                  concat_axis=concat, tiled=True)
        q2, k2, v2 = (a2a(t, 1, 2) for t in (q, k, v))
        o = flash_attention(q2, k2, v2, causal=causal, scale=scale)
        return a2a(o, 2, 1)

    spec = P(batch_axis, None, axis_name, None)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)
