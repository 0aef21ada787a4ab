"""``paged_attention``'s kernel compiled for a described v5e
(tests/tpu_compile_test.py says how and why) at the serve phase's pool
— 8 slots x max_len 1024 at block 16 -> a [512, 8, 16, 16, dk] pool and
a 64-column block table — at C = 1 (decode), gamma+1 (speculative
scoring) and the prefill chunk; and every kernel's name in the text
lowered for the chip.
"""

import pytest

from tpu_compile_test import _compiled_text, chip, topo  # noqa: F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops import flash_attention as FA  # noqa: E402
from paddle_tpu.ops.flash_attention import flash_attention  # noqa: E402
from paddle_tpu.ops.paged_attention import paged_attention  # noqa: E402


_SLOTS, _LAYERS, _HEADS, _BS, _NBMAX = 8, 8, 16, 16, 64
_NB = _SLOTS * _NBMAX
_GAMMA, _CHUNK = 4, 16
_DECODE, _SPEC, _PREFILL = (_SLOTS, 1), (_SLOTS, _GAMMA + 1), (1, _CHUNK)
# the engine's calling shape — the full [NB, L, H, bs, dk] pool — at
# every (C, dk); the per-layer 4-D slice at the decode shape
_PAGED_CASES = [
    pytest.param(rc, dk, True, id="%s-dk%d-pool5d" % (name, dk))
    for name, rc in (("decode", _DECODE), ("spec", _SPEC),
                     ("prefill", _PREFILL))
    for dk in (64, 128)
] + [pytest.param(_DECODE, 64, False, id="decode-dk64-layer4d")]


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16,
                                        jnp.int8],
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("rows_c,dk,full_pool", _PAGED_CASES)
def test_paged_attention_compiles_for_v5e(chip, pool_dtype, rows_c, dk,
                                          full_pool):
    rows, c = rows_c

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    pool_shape = ((_NB, _LAYERS, _HEADS, _BS, dk) if full_pool
                  else (_NB, _HEADS, _BS, dk))
    layer = 3 if full_pool else None
    avals = [aval((rows, _HEADS, c, dk), jnp.float32),
             aval(pool_shape, pool_dtype), aval(pool_shape, pool_dtype),
             aval((rows, _NBMAX), jnp.int32), aval((rows, c), jnp.int32)]
    if pool_dtype == jnp.int8:
        avals += [aval(pool_shape[:-1], jnp.float32)] * 2

    def fn(q, pk, pv, btab, qpos, ks=None, vs=None):
        return paged_attention(q, pk, pv, btab, qpos, k_scale=ks,
                               v_scale=vs, layer=layer, force="pallas")

    assert "tpu_custom_call" in _compiled_text(fn, *avals)


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd", "flash_bwd_dq",
                                    "flash_bwd_dkv", "paged_decode"])
def test_kernel_name_is_in_the_lowered_text(chip, monkeypatch, kernel):
    """The name a profile of the chip shows for each kernel (ISSUE 24):
    the ``kernel_name`` of its ``tpu_custom_call`` in the text lowered
    for the v5e. The one backward kernel where T 1024 is one block
    (bf16), the two where it is streamed (T 4096) over the ONE streamed
    kernel's byte bound."""
    monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)
    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    if kernel == "paged_decode":
        pool = aval((_NB, _LAYERS, _HEADS, _BS, 64), jnp.float32)
        text = jax.jit(lambda q, pk, pv, btab, qpos: paged_attention(
            q, pk, pv, btab, qpos, layer=3, force="pallas")).lower(
            aval((_SLOTS, _HEADS, 1, 64), jnp.float32), pool, pool,
            aval((_SLOTS, _NBMAX), jnp.int32),
            aval((_SLOTS, 1), jnp.int32)).as_text()
    else:
        streamed = kernel in ("flash_bwd_dq", "flash_bwd_dkv")
        q = aval((2, 16, 4096, 64) if streamed else (8, 16, 1024, 64),
                 jnp.bfloat16)

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, force="pallas"
                                   ).astype(jnp.float32).sum()

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).as_text()
    assert 'kernel_name = "%s"' % kernel in text
