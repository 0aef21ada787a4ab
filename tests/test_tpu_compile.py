"""Compile the main paths' Pallas kernels for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler that ships with jaxlib
compiles for a topology that is described, and raises what the chip's
compiler would raise (block shapes off the (8, 128) tiling, scoped
VMEM overrun, ...) — the class of fault interpret mode cannot see.
``paged_attention``'s Pallas path passed every interpret-mode test
while being refused by the compiler at every serving shape; these
compiles are what keeps that from recurring. Nothing runs, so this says
nothing about results or times (chip_smoke.py does, on the chip).

Shapes are the two main paths' at real width: the transformer-large
train step's flash attention (and the XL head dim), the benchmark's
cell (``opt350m_train``: batch 4 x 2048, 16 heads of 64), and the serve
phase's pool — 8 slots x max_len 1024 at block 16 -> a [512, 8, 16,
16, dk] pool and a 64-column block table — at C = 1 (decode), gamma+1
(speculative scoring) and the prefill chunk.
"""

import functools
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from paddle_tpu.ops import flash_attention as FA  # noqa: E402
from paddle_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention, flash_bthd)
from paddle_tpu.ops.paged_attention import paged_attention  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    """A described v5e host of four chips. The persistent compile
    cache is off around the module: a compile for a described chip is
    written to it but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip("cannot describe a v5e topology: %r" % (e,))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One of its chips as a sharding."""
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()


@pytest.mark.parametrize("shape", [(8, 16, 1024, 64), (8, 8, 1024, 128),
                                   (4, 16, 2048, 64)],
                         ids=["large_dk64", "xl_dk128", "opt350m_cell"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles_for_v5e(chip, shape, direction):
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, force="pallas")

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = _compiled_text(fn, q, q, q)
    # forward is one kernel; backward re-runs it and adds one more, all
    # of T being one block at each of these shapes
    assert text.count("tpu_custom_call") == (1 if direction == "fwd"
                                             else 2)


# the projections' own layout (PR 29): (B, T, H, D, dtype) and the
# backward's kernels (PR 31). The benchmark's cell, two heads of 64 to
# a block and all of T in it: one backward kernel; the same in float32,
# where T 2048 is two blocks, and OLMoE's shape, one head of 128 to a
# block, T 4096 streamed: ONE kernel too since ISSUE 39, dq for all rows
# held in VMEM; and OLMoE's shape over that kernel's byte bound (set to
# nothing here; on the chip a T above 32,768): the two kernels.
_ONE, _TWO = ["flash_bwd"], ["flash_bwd_dq", "flash_bwd_dkv"]
_BTHD = [pytest.param(4, 2048, 16, 64, jnp.bfloat16, _ONE,
                      id="opt350m_cell"),
         pytest.param(4, 2048, 16, 64, jnp.float32, _ONE,
                      id="opt350m_cell_f32"),
         pytest.param(2, 4096, 16, 128, jnp.bfloat16, _ONE,
                      id="olmoe_T4k_dk128"),
         pytest.param(2, 4096, 16, 128, jnp.bfloat16, _TWO,
                      id="olmoe_T4k_dk128_over_the_bound")]


@pytest.mark.parametrize("b, t, h, d, dtype, backward", _BTHD)
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_bthd_compiles_for_v5e(chip, monkeypatch, b, t, h, d, dtype,
                                     backward, direction):
    if backward == _TWO:
        monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)
    q = jax.ShapeDtypeStruct((b, t, h * d), dtype, sharding=chip)

    def fwd(q, k, v):
        return flash_bthd(q, k, v, h, causal=True, force="pallas")

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = _compiled_text(fn, q, q, q)
    names = ["flash_fwd"] + (backward if direction == "bwd" else [])
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert "%" + name + "." in text or "%" + name + " " in text


@pytest.mark.parametrize("t, asks", [(2048, False), (4096, True)],
                         ids=["one_block", "streamed"])
def test_only_the_streamed_backward_asks_for_scoped_vmem(chip, t, asks):
    """All of T in one block lives in the compiler's default, as PR 31's
    kernel did: a call that asks for more than the default, by however
    little, loses the matmuls round it their operands staged in VMEM
    (opt350m_train: 0.7% of a step, PR 39). Streamed, dq for all rows is
    resident and the kernel asks for what its shapes need."""
    q = jax.ShapeDtypeStruct((2, t, 16 * 64), jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        return flash_bthd(q, k, v, 16, causal=True,
                          force="pallas").astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text()
    assert ("scoped_memory_configs" in text) == asks


def test_nothing_moves_a_head_between_a_projection_and_the_kernels(chip):
    """The cell's attention layer, projections included, forward and
    backward: the step compiled for the v5e has no `transpose` and no
    `copy` of a [4, 2048, 1024] bf16 operand (under any factoring of
    its dimensions), and every such operand keeps the layout the
    projections' matmuls write, H*D minor. (Through the [B, H, T, D]
    wrapper under a model that splits heads it held nine such copies.)"""
    import math
    import re
    b, t, h, d = 4, 2048, 16, 64
    x = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=chip)
    w = jax.ShapeDtypeStruct((h * d, h * d), jnp.bfloat16, sharding=chip)

    def layer(x, wq, wk, wv, wo):
        a = flash_bthd(x @ wq, x @ wk, x @ wv, h, causal=True,
                       force="pallas")
        return (a @ wo).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(layer, argnums=(0, 1, 2, 3, 4)),
                          x, w, w, w, w)
    assert text.count("tpu_custom_call") == 2      # flash_fwd, flash_bwd
    results = re.findall(
        r"= bf16\[([\d,]+)\]\{([\d,]+)[^}]*\} ([\w-]+)\(", text)
    big = [(dims, layout, op) for dims, layout, op in results
           if math.prod(int(n) for n in dims.split(",")) == b * t * h * d]
    assert len(big) > 10
    assert not [r for r in big if r[2] in ("copy", "transpose")]
    assert {(dims, layout) for dims, layout, _ in big} \
        == {("4,2048,1024", "2,1,0")}


def test_a_region_keeps_the_flash_results_and_runs_the_forward_once(chip):
    """Two window layers at Trinity's widths (hidden 2048, 32 query
    heads of 128 reading 4, one sequence of 16,384 under a window of
    2048), projections round the kernels, each layer a recompute region
    as `recompute_block` lowers one (ISSUE 42): the step compiled for the
    v5e runs `flash_fwd` once a layer and `flash_bwd` once a layer, where
    under a bare jax.checkpoint a forward runs again before its backward;
    and what it holds more than the bare compile, by the compiler's own
    count of temporaries, is at most the named values, out [T, 4096]
    bf16 and lse [32, T] float32 a layer, and 1 MiB."""
    import collections
    import re
    from paddle_tpu.ops import control_flow as CF
    layers, t, hidden, h, hkv, d = 2, 16384, 2048, 32, 4, 128

    def aval(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    x = aval(1, t, hidden)
    ws = [(aval(hidden, h * d), aval(hidden, hkv * d), aval(hidden, hkv * d),
           aval(h * d, hidden))] * layers

    def layer(x, wq, wk, wv, wo):
        a = flash_bthd(x @ wq, x @ wk, x @ wv, h, causal=True,
                       force="pallas", n_kv_head=hkv, window=2048)
        return x + a @ wo

    def compiled(policy):
        def loss(x, ws):
            for w in ws:
                x = jax.checkpoint(layer, policy=policy)(x, *w)
            return x.astype(jnp.float32).sum()

        step = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, ws).compile()
        kernels = re.findall(r"^\s*%?(flash_\w+?)[.\d]* = ", step.as_text(),
                             re.M)
        return (dict(collections.Counter(kernels)),
                step.memory_analysis().temp_size_in_bytes)

    kernels, temporaries = compiled(CF._region_policy)
    bare_kernels, bare_temporaries = compiled(None)
    assert kernels == {"flash_fwd": layers, "flash_bwd": layers}
    assert bare_kernels["flash_fwd"] > layers
    assert bare_kernels["flash_bwd"] == layers
    named = layers * (t * h * d * 2 + h * t * 4)
    assert bare_temporaries < temporaries <= bare_temporaries + named + 2**20


def test_flash_bthd_lowers_under_shard_map_dp2_tp2(topo, monkeypatch):
    """ParallelExecutor's dp2 x tp2 form of the op: batch over dp, the
    heads (a slice of the last dimension) over tp, eight heads a
    device, whole blocks of two. The dispatch asks JAX for its backend,
    which is the CPU here: the test answers for the described chip."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops.parallel_ops import _dense_attention
    monkeypatch.setattr(fa, "_on_tpu", lambda x: True)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    q = jax.ShapeDtypeStruct(
        (8, 2048, 1024), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "tp")))

    def loss(q, k, v):
        return _dense_attention(q, k, v, 16, True, 0.125, mesh=mesh
                                ).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert text.count("tpu_custom_call") == 2      # flash_fwd, flash_bwd
    # a device's shard is what its kernels see: no gather of q, k, v
    assert "bf16[4,2048,512]" in text
    assert "all-gather" not in text and "all-to-all" not in text


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


# ISSUE 32: the block-diffusion cell's shapes. 32 query heads of 128
# reading 4 key/value heads at T 4096 (streamed 1024-blocks, ONE backward
# kernel since ISSUE 39), under the three forms of the block-granular mask; the
# attention of the whole objective (since ISSUE 37 the third form, one call
# of each kernel over [noised; clean] rows and nothing outside them); and
# the dropless expert layer at 16,384 rows over 16 held of 128 experts,
# whose grouped matmuls are XLA's own `ragged-dot` kernels.
@pytest.mark.parametrize("form", [
    {}, {"strict": True}, {"own_block": True}],
    ids=["block_causal", "block_causal_strict", "block_causal_own"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_grouped_kv_block_causal_compiles_for_v5e(chip, form, direction):
    b, t, h, hkv, d = 2, 4096, 32, 4, 128
    t *= 2 if "own_block" in form else 1     # [noised; clean] rows
    q = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, t, hkv * d), jnp.bfloat16, sharding=chip)

    def fwd(q, k, v):
        return flash_bthd(q, k, v, h, causal=True, force="pallas",
                          n_kv_head=hkv, mask_block=4, **form)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = _compiled_text(fn, q, kv, kv)
    names = ["flash_fwd"] + (_ONE if direction == "bwd" else [])
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert "%" + name + "." in text or "%" + name + " " in text


def test_block_diffusion_attention_compiles_for_v5e(chip):
    """[noised; clean] rows of one step's two sequences, forward and
    backward: ONE call of each kernel (ISSUE 37: the own-block form;
    ISSUE 39: the backward is one kernel) and
    no [T, T] tensor: the largest float32 buffer the program names is an
    operand's size. Nothing of q's size is made outside the kernels,
    forward or backward: no dot, slice, concatenate, pad or transpose
    (the halves are addressed by the kernels' block offsets, the merge
    is the streaming softmax's); what is left is the sums of dk and dv
    over each group of 8 query heads and their two halves put end to
    end, an eighth of q's size."""
    import math
    import re
    from paddle_tpu.ops import block_diffusion as BD
    b, t, h, hkv, d = 2, 4096, 32, 4, 128
    q = jax.ShapeDtypeStruct((b, 2 * t, h * d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, 2 * t, hkv * d), jnp.bfloat16,
                              sharding=chip)

    def loss(q, k, v):
        return BD.attention(q, k, v, h, hkv, 4, force="pallas").astype(
            jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") == 2
    for name in ["flash_fwd"] + _ONE:
        assert "%" + name + "." in text or "%" + name + " " in text
    size = lambda dims: math.prod(int(x) for x in dims.split(","))
    assert max(size(dims) for dims in re.findall(r"f32\[([\d,]+)\]", text)
               ) <= b * 2 * t * h * d
    moved = [line.strip() for line in text.splitlines() for made in
             [re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* "
                       r"(dot|slice|concatenate|pad|transpose)\(", line)]
             if made and size(made.group(1)) >= b * 2 * t * h * d]
    assert not moved, moved


def test_nothing_relays_q_or_k_between_a_projection_and_the_kernels(chip):
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
    op alone (`rms_norm` grouped, `rope`) lowers to the same kernels."""
    import math
    import re
    from paddle_tpu.ops import rotary
    b, t, h, hkv, d, dm = 2, 4096, 32, 4, 128, 2048
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=chip)
    scale = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=chip)
    avals = (sds(b, t, dm), sds(dm, h * d), sds(dm, hkv * d),
             sds(dm, hkv * d), scale, scale)
    moves = ("reshape", "concatenate", "pad", "slice", "copy", "transpose")

    def layer(fused, x, wq, wk, wv, sq, sk):
        if fused is None:
            turn = lambda y, s, n: y
        elif fused:
            turn = lambda y, s, n: rotary.norm_rope(y, s, n, 1e6, t // 2,
                                                    1e-6, force="pallas")
        else:
            turn = lambda y, s, n: rotary.norm_rope(
                rotary.norm_rope(y, s, n, force="pallas"), None, n, 1e6,
                t // 2, force="pallas")
        return flash_bthd(turn(x @ wq, sq, h), turn(x @ wk, sk, hkv), x @ wv,
                          h, causal=True, force="pallas", n_kv_head=hkv,
                          mask_block=4)

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
    bare = moved(_compiled_text(grad(None), *avals))
    for fused, n in ((True, 2), (False, 4)):
        text = _compiled_text(functools.partial(layer, fused), *avals)
        assert calls(text, "qk_norm_rope_fwd") == n
        assert text.count("tpu_custom_call") == n + 1
        assert len(sized(text)) >= n and not moved(text)
        text = _compiled_text(grad(fused), *avals)
        assert calls(text, "qk_norm_rope_fwd") == n
        assert calls(text, "qk_norm_rope_bwd") == n
        # the flash forward and, since ISSUE 39, ONE flash backward
        assert text.count("tpu_custom_call") == 2 * n + 2
        assert not moved(text, b * t * h * d)
        assert set(moved(text)) == {"pad"} == set(bare)
        assert len(moved(text)) <= len(bare)


@pytest.mark.parametrize("shape", [(16384, 2048, 768, 128, 16, 8),
                                   (4096, 3584, 1024, 64, 8, 4)],
                         ids=["sdar_train_bd4k", "xing4_train_T4k"])
def test_routed_experts_compile_for_v5e(chip, shape):
    """A routed cell's expert layer, forward and backward: grouped
    matmuls as XLA's ragged-dot kernels inside the two loops over chunks,
    on a chunk's rows (32,768; 4,096): no hidden activation of the worst
    case's N * top_k rows exists. ISSUE 35: a chunk's rows go back to
    their tokens by `moe_scatter_add_rows` (once forward, once for dx)
    and each accumulator leaves its slab by `moe_leave_slab`, under
    their own names; XLA scatters nothing of x's width (what is left of
    that kind is the pairs' weights, one number a place), and its
    gathers of a chunk's rows stay: x forward, x and dout backward."""
    import re
    from paddle_tpu.parallel import moe
    n, d, f, e, held, k = shape
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=chip)
    x = sds((n, d), jnp.float32)
    wr = sds((d, e), jnp.float32)
    w_in, w_out = sds((held, d, f), jnp.bfloat16), sds((held, f, d),
                                                       jnp.bfloat16)

    def loss(x, wr, wg, wu, wd):
        out, aux, _, _ = moe.routed_experts(x, wr, wg, wu, wd, e, 0, k,
                                            force="pallas")
        return out.astype(jnp.float32).sum() + aux

    # the value too: XLA drops a forward whose result nobody reads
    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                          x, wr, w_in, w_in, w_out)
    assert "ragged-dot" in text and "while" in text
    hidden = {int(rows) for rows in re.findall(
        r"(?:bf16|f32)\[(\d+),%d\]" % f, text)}
    cap = 2 * n * k * held // e
    assert hidden and max(hidden) == cap
    calls = lambda name: len(re.findall(
        r"%%%s[.\d]* = \S+ custom-call\(" % name, text))
    assert calls("moe_scatter_add_rows") == 2
    assert calls("moe_leave_slab") == 2
    wide = lambda kind: [line for line in text.splitlines() if re.search(
        r" %s\(" % kind, line) and re.search(r"\[\d+,%d\]" % d, line)]
    assert not wide("scatter"), wide("scatter")[:2]


# --------------------------------------------------------------------------
# ISSUE 34: latent attention's score of two parts at the cell
# xing4_train_T4k's shape, T 4096 streamed: q_nope / k_nope / v
# [1, 4096, 32 x 128], q_pe [1, 4096, 32 x 64] and ONE k_pe [1, 4096, 64].
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_two_part_score_compiles_for_v5e(chip, direction):
    """The three streamed kernels with a second score part, under their
    own names; nothing of k_pe's size times the heads exists (the shared
    key is repeated to ONE 128-lane tile, [1, 4096, 128]), no operand
    is padded to 256 lanes a head, and dk_pe's sum over the 32 heads
    is made in the kernel: the only results between the kernels and the
    gradients are the fold of that one tile."""
    import math
    import re
    b, t, h, d, d2 = 1, 4096, 32, 128, 64
    sds = lambda lanes: jax.ShapeDtypeStruct((b, t, lanes), jnp.bfloat16,
                                             sharding=chip)
    avals = (sds(h * d), sds(h * d), sds(h * d), sds(h * d2), sds(d2))

    def fwd(q, k, v, q2, k2):
        return flash_bthd(q, k, v, h, causal=True, force="pallas", q2=q2,
                          k2=k2)

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss,
                                                 argnums=(0, 1, 2, 3, 4))
    text = _compiled_text(fn, *avals)
    names = ["flash_fwd"] + (_TWO if direction == "bwd" else [])
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert "%" + name + "." in text or "%" + name + " " in text
    sizes = {math.prod(int(x) for x in dims.split(","))
             for dims in re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text)}
    # operands and gradients as they come, the statistics' rows, the
    # one tile of k_pe, and nothing wider
    assert max(sizes) == b * t * h * d
    assert b * t * h * 2 * d not in sizes and b * t * h * (d + d2) not in sizes


# ISSUE 38: a window bound. The cell `trinity_train_T16k`'s window
# layers: one packed 16,384-token sequence, 32 query heads of 128 reading
# 4 key/value heads, a window of 2048 keys: streamed 1024-blocks whose key
# axis holds the band's three steps alone.
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_windowed_attention_compiles_for_v5e(chip, direction):
    """q [1, 16384, 4096] against k, v [1, 16384, 512] under a window of
    2048: the forward and ONE backward kernel, and no [T, T] value: the
    largest buffer the program names is an operand's size."""
    import math
    import re
    b, t, h, hkv, d = 1, 16384, 32, 4, 128
    q = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, t, hkv * d), jnp.bfloat16, sharding=chip)

    def fwd(q, k, v):
        return flash_bthd(q, k, v, h, causal=True, force="pallas",
                          n_kv_head=hkv, window=2048)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = _compiled_text(fn, q, kv, kv)
    names = ["flash_fwd"] + (_ONE if direction == "bwd" else [])
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert "%" + name + "." in text or "%" + name + " " in text
    size = lambda dims: math.prod(int(x) for x in dims.split(","))
    assert max(size(dims) for dims in re.findall(r"[fb]\w*\[([\d,]+)\]", text)
               ) <= b * t * h * d


# ISSUE 39: the streamed backward as ONE kernel, at the two cells' shapes
# that stream T: block diffusion's [noised; clean] rows (8,192 a sequence)
# and one packed sequence of 16,384 rows, plain causal (Trinity's full
# layer) and under a window of 2048 (its four window layers).
@pytest.mark.parametrize("b, t, form", [
    (2, 8192, {"mask_block": 4, "own_block": True}),
    (1, 16384, {}), (1, 16384, {"window": 2048})],
    ids=["own_block_2x8192", "causal_16384", "window_2048_of_16384"])
def test_one_streamed_backward_kernel_compiles_for_v5e(chip, b, t, form):
    """32 query heads of 128 reading 4 key/value heads, forward and
    backward: the compiler takes the backward with the scoped VMEM it
    asks for (dq for all rows in float32 and its output block twice pass
    the 16 MB default: 4 + 2 x 2 MB at 8,192 rows, 8 + 2 x 4 at 16,384),
    which is what its shapes say and well under a core's 128 MiB; ONE
    backward custom call, named flash_bwd, whose results are dq, dk, dv
    (the noised halves' dk, dv under the own-block form) and no row
    statistic: the only [B*H, 1, T] value in the program is the lse the
    forward hands it, no delta goes through HBM; and no [T, T] value."""
    import math
    import re
    h, hkv, d = 32, 4, 128
    q = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, t, hkv * d), jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        return flash_bthd(q, k, v, h, causal=True, force="pallas",
                          n_kv_head=hkv, **form).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)
    asked = [int(x) for x in re.findall(
        r'scoped_memory_configs[^\]]*?size\\22: (\d+)', lowered.as_text())]
    rows_k = t // 2 if "own_block" in form else t
    assert asked == [FA._one_kernel_vmem_bytes(
        t, d, 1024, 1024, 1, 2, 4, 4 if "own_block" in form else 2, 1)]
    assert 16 * 2 ** 20 < asked[0] < 100 * 2 ** 20
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 2
    calls = dict(re.findall(r"%(flash_\w+?)(?:\.\d+)? = (.*?) custom-call\(",
                            text))
    assert sorted(calls) == ["flash_bwd", "flash_fwd"]
    stat = "f32[%d,1,%d]" % (b * h, t)
    assert stat in calls["flash_fwd"] and stat not in calls["flash_bwd"]
    assert len(re.findall(r"\w+\[[\d,]+\]", calls["flash_bwd"])) == (
        5 if "own_block" in form else 3)
    assert "f32[%d,%d,%d]" % (b, rows_k, h * d) in calls["flash_bwd"]
    made = set(re.findall(r"= " + re.escape(stat) + r"\S* ([\w-]+)\(", text))
    assert made <= {"get-tuple-element"}, made     # flash_fwd's lse alone
    size = lambda dims: math.prod(int(x) for x in dims.split(","))
    assert max(size(dims) for dims in re.findall(r"[fb]\w*\[([\d,]+)\]", text)
               ) <= b * t * h * d


# ISSUE 40: the selective scan's chunked kernel pair at the cell
# `phi4flash_train_T8k`'s shape (one packed 8,192-token sequence, 5,120
# channels of 16 states, bf16 operands), and differential attention's
# window layer there (40 query and 20 key/value heads of 64, values of
# 128, a window of 512).
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_selective_scan_compiles_for_v5e(chip, direction):
    """s and dt [1, 8192, 5120] bf16, 16 states: ONE kernel a direction
    (the backward re-runs the forward's), named as a device trace will
    show them; the compiled program holds no [8192, 5120, 16] value, and
    no while loop: time is walked by the kernels' grids and the loops
    inside them, not by 8,192 trips of XLA's."""
    import math
    import re
    from paddle_tpu.ops.selective_scan import selective_scan
    b, t, c, n = 1, 8192, 5120, 16
    sd = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)
    avals = (sd((b, t, c)), sd((b, t, c)), sd((c, n), jnp.float32),
             sd((b, t, n)), sd((b, t, n)), sd((c,), jnp.float32))

    def fwd(*a):
        return selective_scan(*a, force="pallas")

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=tuple(range(6)))
    text = _compiled_text(fn, *avals)
    names = ["selective_scan_fwd"] + (["selective_scan_bwd"]
                                      if direction == "bwd" else [])
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert "%" + name + "." in text or "%" + name + " " in text
    assert " while(" not in text
    size = lambda dims: math.prod(int(x) for x in dims.split(","))
    # the largest: B_t or C_t over 128 lanes, [1, 8192, 16, 128]
    assert max(size(dims) for dims in re.findall(r"[fb]\w*\[([\d,]+)\]", text)
               ) <= b * t * c < b * t * c * n


def test_differential_window_attention_compiles_for_v5e(chip):
    """q [1, 8192, 2560] against k, v [1, 8192, 1280] under a window of
    512, forward and backward: each softmax the forward and ONE backward
    kernel of the streamed set, no dense lowering and no [T, T] value:
    the largest buffer the program names is q's size."""
    import math
    import re
    from paddle_tpu.ops.flash_attention import flash_diff_bthd
    b, t, h, hkv, d = 1, 8192, 40, 20, 64
    q = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, t, hkv * d), jnp.bfloat16, sharding=chip)
    dense = lambda: sum(
        v for key, v in FA._LOWERINGS.snapshot().items()
        if key[FA._LOWERINGS.label_names.index("path")] == "dense")
    before = dense()

    def loss(q, k, v):
        a1, a2 = flash_diff_bthd(q, k, v, h, hkv, window=512,
                                 force="pallas")
        return (a1.astype(jnp.float32) - 0.5 * a2.astype(jnp.float32)).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert dense() == before
    assert text.count("tpu_custom_call") == 4
    assert len(re.findall(r"%flash_fwd(?:\.\d+)? = ", text)) == 2
    assert len(re.findall(r"%flash_bwd(?:\.\d+)? = ", text)) == 2
    size = lambda dims: math.prod(int(x) for x in dims.split(","))
    assert max(size(dims) for dims in re.findall(r"[fb]\w*\[([\d,]+)\]", text)
               ) <= b * t * h * d


# the hyper-connections' kernels (ISSUE 43) at the cell xing4_train_T4k's
# shape: a float32 stream [4096, 4 x 3584] round a stand-in sublayer that
# hands back bfloat16, two sublayers to a recompute region as a layer of
# the model has them.
def test_hyper_connection_kernels_compile_for_v5e(chip):
    """A sublayer-pass is four custom calls under the scope
    `hyper_connection`: `hc_mix_fwd` and `hc_merge_fwd` forward,
    `hc_merge_bwd` and `hc_mix_bwd` backward (a region's second forward
    runs `hc_mix_fwd` again, and `hc_merge_fwd` where a later sublayer
    reads its result). XLA itself makes NO pass over the stream between
    "widen" and "narrow": no fusion, copy or add of the compiled step has
    a float32 [4096, 14336] operand or result but those two stages'; the
    stream's two cotangents a sublayer are summed inside `hc_mix_bwd`.
    Each kernel asks for the scoped VMEM its blocks come to."""
    import collections
    import re
    from paddle_tpu.ops import control_flow as CF
    from paddle_tpu.ops import hyper_connection as HC
    n, d, rows, regions = 4, 3584, 4096, 2
    c, width = n * (n + 2), n * d
    sd = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)

    def sublayer(x, proj, alpha, bias, w):
        with jax.named_scope("hyper_connection.1"):
            h, post, res, through = HC.mix_stage(
                x, proj, alpha, bias, n, 20, 1e-6, (-30.0, 30.0),
                force="pallas")
        y = jnp.tanh(h.astype(jnp.bfloat16) @ w)
        with jax.named_scope("hyper_connection.2"):
            return HC.merge_stage(through, post, res, y, n, force="pallas")

    def layer(x, first, second):
        return sublayer(sublayer(x, *first), *second)

    def loss(e, params):
        with jax.named_scope("hyper_connection.0"):
            x = jnp.tile(e, (1, n))
        for p in params:
            x = jax.checkpoint(layer, policy=CF._region_policy)(x, *p)
        with jax.named_scope("hyper_connection.3"):
            return jnp.square(sum(HC._lanes(x, n))).sum()

    p = (sd((width, c)), sd((3,)), sd((c,)), sd((d, d), jnp.bfloat16))
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        sd((rows, d)), [(p, p)] * regions)
    asked = {int(x) for x in re.findall(
        r'scoped_memory_configs[^\]]*?size\\22: (\d+)', lowered.as_text())}
    stream, small = 4 * width, 4 * 128
    w_bytes, dpt = 2 * width * 128, 4 * width * 72
    blocks = {      # a grid step's rows, a row's bytes, the resident bytes
        "hc_mix_fwd": (128, stream + 4 * d + small, w_bytes + 2 * small),
        "hc_merge_fwd": (64, 2 * stream + 2 * d + small, 0),
        "hc_merge_bwd": (64, 3 * stream + 4 * d + 2 * small, 0),
        "hc_mix_bwd": (64, 3 * stream + 4 * d + 3 * small,
                       w_bytes + 2 * small + dpt)}
    # row blocks are double buffered, resident ones fetched once
    want = {k: 2 * bm * row + resident + HC._SPARE_BYTES
            for k, (bm, row, resident) in blocks.items()}
    assert HC._block_rows(rows, width, 1) == 128
    assert HC._block_rows(rows, width, 2) == HC._block_rows(rows, width, 3) \
        == 64
    # forward, a region's second forward (mix twice, merge once), backward
    calls = {"hc_mix_fwd": 4 * regions, "hc_merge_fwd": 3 * regions,
             "hc_merge_bwd": 2 * regions, "hc_mix_bwd": 2 * regions}
    assert asked == set(want.values()), (asked, want)
    assert max(asked) < 48 * 2 ** 20

    text = lowered.compile().as_text()
    entry = text[text.index("ENTRY"):]
    big = "f32[%d,%d]" % (rows, width)
    passes = collections.Counter()
    for line in entry.splitlines():
        head, _, meta = line.partition(", metadata")
        made = re.match(r"\s*(?:ROOT )?%?([\w.\-]+?)(?:\.\d+)? = .*? "
                        r"([a-z][\w\-]*)\(", head)
        if not made or big not in head or made.group(2) in (
                "parameter", "get-tuple-element", "tuple", "bitcast"):
            continue
        scope = re.search(r'op_name="[^"]*?(hyper_connection\.\d)', meta)
        assert scope, line[:300]
        passes[made.group(1) if made.group(2) == "custom-call"
               else "xla in " + scope.group(1)] += 1
    assert {k: v for k, v in passes.items() if k in calls} == calls, passes
    # "widen" is fused into the first kernel's operand or is one fusion;
    # "narrow"'s backward is one fusion (the loss's gradient, tiled)
    assert set(passes) - set(calls) <= {"xla in hyper_connection.0",
                                        "xla in hyper_connection.3"}, passes
    assert sum(v for k, v in passes.items() if k not in calls) <= 3, passes
