"""Block-native paged attention — decode attention that walks only the
blocks a request actually holds (ISSUE 20).

The serving engine's PR-10 paged decode gathered the whole KV pool
through each slot's block table and sliced back to the dense
``[.., max_len, ..]`` axis, so attention compute AND bandwidth scaled
with pool capacity rather than tokens cached. This module is the
kernel tier that fixes it: the vLLM-PagedAttention kernel shape fused
with FlashAttention-style online softmax (the streaming m/l/acc
machinery of ``ops/flash_attention.py``), with three paths:

  * ``lax``   — a ``lax.fori_loop`` over ONLY the first ``nblk``
    block-table columns (the longest live chain in the batch, a
    DYNAMIC bound — compute proportional to blocks held, not pool
    width). The CPU fallback and the reference semantics.
  * ``pallas``/``interpret`` — the TPU kernel: grid (S, H, NBmax)
    with the block table + per-slot chain lengths as scalar-prefetch
    operands (``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec
    index map chases each slot's physical chain. Blocks past a slot's
    chain skip their matmuls (``pl.when``) and clamp the index map to
    the last live block, which Pallas dedupes into a no-op re-fetch.

Shapes: ``q`` [S, H, C, dk] (C = 1 for the single decode step, γ+1
for speculative scoring, the chunk length for prefill; q arrives
PRE-SCALED by dk**-0.5), per-layer pool slices ``pool_k``/``pool_v``
[NB, H, bs, dk], block table ``btab`` [S, NBmax] int32, per-query key
bound ``qpos`` [S, C] int32 (cache positions <= qpos[s, c] attend —
the paged twin of the dense causal bias). Output is [S, H, C, dk]
float32; the caller casts back to its compute dtype.

Identity contract (tests/test_paged_attention.py + the serving
lattice): at fp32 the online softmax is algebraically the dense
softmax — outputs agree to accumulation-order rounding (~1e-6
relative), and greedy/speculative TOKEN streams through the serving
engine are pinned bitwise-identical to the dense-gather escape hatch
(`serving_block_kernel=0`).

Quantized KV (int8, fp8 hook): the pool stores codes plus ONE float32
scale per cached vector (per block/position/head, beside the pool —
``k_scale``/``v_scale`` [NB, H, bs]); ``quantize_kv`` runs on cache
write, the kernel's block loop dequantizes as it streams. Error
budget: symmetric per-vector int8 rounds each element to within
scale/2 = amax/254, a worst-case relative error of 1/254 ≈ 0.4% per
element; attention output error stays the same order (softmax weights
are a convex combination), pinned at rtol 2e-2 in tests like the bf16
serving pass.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import _on_tpu

_NEG_INF = -1e30

__all__ = ["paged_attention", "kv_quant_spec", "quantize_kv",
           "dequantize_kv"]


# --------------------------------------------------------------------------
# KV quantization: codes stored at the pool dtype, one f32 scale per
# cached (block, position, head) vector stored beside the pool.
def kv_quant_spec(kind):
    """(pool dtype, qmax) for a kv-quant mode name. int8 is the
    production path; fp8 (e4m3) is the hook."""
    if kind in (None, "", "none", "off"):
        return None
    if kind == "int8":
        return jnp.int8, 127.0
    if kind == "fp8":
        return jnp.float8_e4m3fn, 448.0
    raise ValueError(
        "unknown kv quantization %r (expected '', 'int8' or 'fp8')"
        % (kind,))


_QMAX = {jnp.dtype(jnp.int8): 127.0,
         jnp.dtype(jnp.float8_e4m3fn): 448.0}


def quantize_kv(x, qdtype):
    """Quantize vectors ``x`` [..., dk] to (codes [..., dk] qdtype,
    scale [...] f32): symmetric per-vector scaling amax/qmax (scale 1
    for all-zero vectors, so block 0's zeros round-trip exactly)."""
    qdtype = jnp.dtype(qdtype)
    qmax = _QMAX[qdtype]
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0.0, amax / qmax, 1.0)
    y = xf / scale[..., None]
    if qdtype == jnp.dtype(jnp.int8):
        codes = jnp.clip(jnp.round(y), -qmax, qmax).astype(qdtype)
    else:
        codes = y.astype(qdtype)
    return codes, scale


def dequantize_kv(codes, scale):
    """codes [..., dk] x scale [...] -> f32 vectors."""
    return codes.astype(jnp.float32) * scale[..., None].astype(
        jnp.float32)


def _maybe_dequant(block, scale_block):
    if scale_block is None:
        return block
    return dequantize_kv(block, scale_block)


# --------------------------------------------------------------------------
# lax fallback: online softmax over a DYNAMIC number of block-table
# columns (lax.fori_loop lowers to a while loop — trip count is the
# longest live chain, not the table width).
def _attend_lax(q, pool_k, pool_v, btab, qpos, nblk, k_scale, v_scale,
                block_group, layer=None):
    s, h, c, dk = q.shape
    bs = pool_k.shape[-2]
    nbmax = btab.shape[1]
    u = max(1, min(int(block_group), nbmax))
    pad = (-nbmax) % u
    if pad:
        # pad table width to a group multiple; padded columns read
        # block 0 and are masked below by kpos > qpos
        btab = jnp.pad(btab, ((0, 0), (0, pad)))
    qf = q.astype(jnp.float32)
    qpos_e = qpos[:, None, :, None]                  # [S, 1, C, 1]

    def pick(pool, scale, cols):
        # [S, u, H, bs, dk]: a FULL [NB, L, ..] pool gathers (block,
        # layer) pairs directly — slicing the layer out first would
        # copy the whole pool, a capacity-proportional cost this
        # kernel exists to avoid
        if layer is None:
            return _maybe_dequant(
                pool[cols], None if scale is None else scale[cols])
        return _maybe_dequant(
            pool[cols, layer],
            None if scale is None else scale[cols, layer])

    def body(t, carry):
        m, l, acc = carry
        col0 = t * u
        cols = lax.dynamic_slice_in_dim(btab, col0, u, axis=1)
        kb = pick(pool_k, k_scale, cols)
        vb = pick(pool_v, v_scale, cols)
        kb = kb.transpose(0, 2, 1, 3, 4).reshape(s, h, u * bs, dk)
        vb = vb.transpose(0, 2, 1, 3, 4).reshape(s, h, u * bs, dk)
        sc = jnp.einsum("shcd,shkd->shck", qf, kb,
                        preferred_element_type=jnp.float32)
        kpos = col0 * bs + jnp.arange(u * bs)
        sc = jnp.where(kpos[None, None, None, :] <= qpos_e, sc,
                       _NEG_INF)
        m_cur = jnp.max(sc, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "shck,shkd->shcd", p, vb.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((s, h, c, 1), _NEG_INF, jnp.float32),
            jnp.zeros((s, h, c, 1), jnp.float32),
            jnp.zeros((s, h, c, dk), jnp.float32))
    trips = lax.div(nblk + (u - 1), jnp.int32(u))
    _, l, acc = lax.fori_loop(0, trips, body, init)
    return acc / jnp.maximum(l, 1e-30)


# --------------------------------------------------------------------------
# Pallas kernel: grid (S, H, NBmax); btab + per-slot chain lengths are
# scalar-prefetch operands so the K/V index maps chase the chain.
def _paged_kernel(btab_ref, chain_ref, q_ref, qpos_ref, k_ref, v_ref,
                  ks_ref, vs_ref, o_ref, m_s, l_s, acc_s, *, bs, nbmax,
                  quant):
    s = pl.program_id(0)
    hi = pl.program_id(1)
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def _scale_row(ref):
        # this head's [1, bs] row of the block's [H, bs] scale tile
        # (leading unit dims: block, and layer for a full pool)
        lead = (0,) * (len(ref.shape) - 2)
        return ref[lead + (pl.ds(hi, 1), slice(None))].reshape(1, bs)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [C, dk]
        # K/V blocks arrive as (1, 1, bs, dk) (per-layer pool) or
        # (1, 1, 1, bs, dk) (full pool, layer picked by the index
        # map) — collapse the leading unit dims either way
        kk = k_ref[...].reshape(bs, -1).astype(jnp.float32)
        vv = v_ref[...].reshape(bs, -1).astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [C, bs]
        if quant:
            # one scale per cached vector factors out of its dot
            # product: scale the [C, bs] scores (and, below, the
            # probabilities), not the [bs, dk] codes
            sc = sc * _scale_row(ks_ref)
        kpos = b * bs + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(kpos <= qpos_ref[0], sc, _NEG_INF)   # [C, 1]
        m_prev = m_s[:]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_s[:] = alpha * l_s[:] + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            p = p * _scale_row(vs_ref)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p, vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:] = m_new

    # chain skip: blocks past this slot's chain contribute nothing —
    # skip their matmuls (the index map clamps their fetch to the last
    # live block, which Pallas dedupes into a no-op)
    pl.when(b < chain_ref[s])(_compute)

    @pl.when(b == nbmax - 1)
    def _final():
        o_ref[0, 0] = acc_s[:] / jnp.maximum(l_s[:], 1e-30)


def _attend_pallas(q, pool_k, pool_v, btab, qpos, k_scale, v_scale,
                   interpret, layer=None):
    s, h, c, dk = q.shape
    bs = pool_k.shape[-2]
    nbmax = btab.shape[1]
    quant = k_scale is not None
    chain = jnp.minimum(jnp.max(qpos, axis=1) // bs + 1,
                        nbmax).astype(jnp.int32)

    def _chase(si, hi, b, tab, ch):
        # physical block of column b in slot si's chain, clamped to the
        # last live block past the chain end (re-fetch dedup)
        blk = tab[si, jnp.minimum(b, ch[si] - 1)]
        if layer is None:
            return (blk, hi, 0, 0)
        return (blk, layer, hi, 0, 0)

    def _chase_sc(si, hi, b, tab, ch):
        blk = tab[si, jnp.minimum(b, ch[si] - 1)]
        if layer is None:
            return (blk, 0, 0)
        return (blk, layer, 0, 0)

    # TPU block rule: the last two dims of every block are multiples
    # of (8, 128) or the array's own. K/V blocks end in the pool's own
    # (bs, dk); qpos rides as [S, C, 1] so its (C, 1) block is the
    # array's own and already the [C, 1] column the mask wants; a
    # scale block carries all H heads' (H, bs) rows of one pool block
    # and the kernel picks its head's row.
    kv_block = ((1, 1, bs, dk) if layer is None
                else (1, 1, 1, bs, dk))
    kv_spec = pl.BlockSpec(kv_block, _chase)
    in_specs = [
        pl.BlockSpec((1, 1, c, dk), lambda si, hi, b, tab, ch:
                     (si, hi, 0, 0)),
        pl.BlockSpec((1, c, 1), lambda si, hi, b, tab, ch: (si, 0, 0)),
        kv_spec, kv_spec,
    ]
    args = [q.astype(jnp.float32),
            qpos.astype(jnp.int32).reshape(s, c, 1), pool_k, pool_v]
    if quant:
        sc_spec = pl.BlockSpec(
            (1, h, bs) if layer is None else (1, 1, h, bs),
            _chase_sc)
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]
    else:
        # placeholder scalars keep the kernel arity fixed
        in_specs += [pl.BlockSpec((1, 1), lambda si, hi, b, tab, ch:
                                  (0, 0))] * 2
        args += [jnp.zeros((1, 1), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, h, nbmax),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, c, dk), lambda si, hi, b, tab, ch:
                               (si, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((c, 1), jnp.float32),
            pltpu.VMEM((c, 1), jnp.float32),
            pltpu.VMEM((c, dk), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, nbmax=nbmax,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, h, c, dk), jnp.float32),
        interpret=interpret,
        name="paged_decode",
    )(btab.astype(jnp.int32), chain, *args)


# --------------------------------------------------------------------------
def _resolve_path(q, force):
    # every (dk, bs, pool dtype) compiles for the v5e — each block's
    # last two dims are its array's own (tests/test_tpu_compile_paged.py) —
    # so a TPU always takes the kernel; there is no shape carve-out
    # that could hand a TPU call to lax unseen
    if force is not None:
        return force
    return "pallas" if _on_tpu(q) else "lax"


def paged_attention(q, pool_k, pool_v, btab, qpos, nblk=None,
                    k_scale=None, v_scale=None, block_group=1,
                    layer=None, force=None):
    """Block-chain paged attention over a shared KV pool.

    q [S, H, C, dk] pre-scaled queries; pool_k/pool_v [NB, H, bs, dk]
    one layer's pool slice, OR the FULL [NB, L, H, bs, dk] pool with
    ``layer`` a static int — the preferred calling shape: both paths
    then gather (block, layer) pairs directly, where slicing the
    layer out first would copy the whole pool (a capacity-
    proportional cost) every step. Pools are f32/bf16, or int8/fp8
    codes with k_scale/v_scale ([NB, H, bs] / [NB, L, H, bs]) beside
    them. btab [S, NBmax] int32 block table; qpos [S, C] int32
    per-query key bound (cache positions <= qpos[s, c] attend).
    ``nblk`` bounds the walk — the longest live chain in the batch, a
    dynamic scalar (defaults to covering max(qpos)); slots whose
    chain the bound does not cover get garbage rows the engine never
    reads (inactive slots), exactly like the dense path's masked
    garbage. ``block_group`` is the lax fallback's blocks-per-trip
    knob (flag ``serving_attn_unroll``).

    force: None = auto (Pallas on TPU, lax elsewhere), or one of
    "lax" / "pallas" / "interpret". Returns [S, H, C, dk] float32.
    """
    if (pool_k.ndim == 5) != (layer is not None):
        raise ValueError(
            "a [NB, L, H, bs, dk] pool needs layer=<int> and a "
            "per-layer [NB, H, bs, dk] slice needs layer=None; got "
            "pool ndim %d, layer %r" % (pool_k.ndim, layer))
    nbmax = btab.shape[1]
    bs = pool_k.shape[-2]
    if nblk is None:
        nblk = jnp.max(qpos) // bs + 1
    nblk = jnp.clip(jnp.asarray(nblk, jnp.int32), 1, nbmax)
    path = _resolve_path(q, force)
    if path == "lax":
        return _attend_lax(q, pool_k, pool_v, btab, qpos, nblk,
                           k_scale, v_scale, block_group, layer=layer)
    return _attend_pallas(q, pool_k, pool_v, btab, qpos, k_scale,
                          v_scale, path == "interpret", layer=layer)


# pallas imports at the end so CPU-only environments import this module
# without a pallas backend (trace-time only — the flash_attention idiom)
from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402
