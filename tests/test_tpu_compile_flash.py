"""The flash kernels compiled for a described v5e
(tests/tpu_compile_test.py says how and why). Shapes are the main
paths' at real width: the transformer-large train step's flash
attention (and the XL head dim) and the benchmark's cell
(``opt350m_train``: batch 4 x 2048, 16 heads of 64), which is their
point: nothing here is cut to a smaller shape. The mask forms' cells
are tests/test_tpu_compile_flash_masks.py's.
"""

import pytest

from tpu_compile_test import _compiled_text, chip, topo  # noqa: F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops import flash_attention as FA  # noqa: E402
from paddle_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention, flash_bthd)


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


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_grouped_heads_of_64_compile_for_v5e_at_32768_rows(chip, direction):
    """32 query heads of 64 reading 8, one sequence of 32,768 (ISSUE
    49): two heads to a block, both of one group, k and v spread under
    the query heads' lanes before the kernels. One forward kernel; the
    gradient adds ONE `flash_bwd`, dq for all 32,768 rows of a block of
    two heads resident in VMEM (16 MB float32, the byte bound's edge),
    and gives dk and dv in k's own shape."""
    t, h, hkv, d = 32768, 32, 8, 64
    q = jax.ShapeDtypeStruct((1, t, h * d), jnp.bfloat16, sharding=chip)
    k = jax.ShapeDtypeStruct((1, t, hkv * d), jnp.bfloat16, sharding=chip)

    def fwd(q, k, v):
        return flash_bthd(q, k, v, h, causal=True, force="pallas",
                          n_kv_head=hkv)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    step = jax.jit(fn).lower(q, k, k).compile()
    text = step.as_text()
    names = ["flash_fwd"] + (["flash_bwd"] if direction == "bwd" else [])
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert "%" + name + "." in text or "%" + name + " " in text
    if direction == "bwd":
        assert [o.shape for o in jax.tree.leaves(step.out_info)] == [
            (1, t, h * d), (1, t, hkv * d), (1, t, hkv * d)]


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


def test_a_region_keeps_its_mul_results_and_runs_each_product_once(chip):
    """Two layers at Phi-4-mini-flash's MLP widths (one sequence of
    8,192, 2,560 -> 10,240 -> 2,560, SiLU-gated, bf16 results of float32
    sums as `mul` makes them under AMP), each a recompute region whose
    gate and up products carry the name a region's plan gives an
    admitted `mul` result (ISSUE 48): the step compiled for the v5e
    makes each [8192, 10240] product ONCE, in the forward, and none
    under `rematted_computation`, where with no name it makes each
    again before the backward; what it holds more, by the compiler's
    own count of temporaries, is at most the named values, two
    [8192, 10240] bf16 a layer, and 1 MiB; and wherever the
    `reduce_precision` that JAX puts on a kept value is left in the
    text, it is the ROOT of the fusion that holds the product's
    `convolution`, not a pass of its own over 168 MB."""
    import re
    from jax.ad_checkpoint import checkpoint_name
    from paddle_tpu.ops import control_flow as CF
    layers, t, d, ffn = 2, 8192, 2560, 10240

    def aval(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    x = aval(t, d)
    ws = [(aval(d, ffn), aval(d, ffn), aval(ffn, d))] * layers

    def mul(x, w):
        return jnp.matmul(x, w, preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16)

    def compiled(name):
        def layer(x, wg, wu, wd):
            gate, up = name(mul(x, wg)), name(mul(x, wu))
            return x + mul(jax.nn.silu(gate) * up, wd)

        def loss(x, ws):
            for w in ws:
                x = jax.checkpoint(layer, policy=CF._region_policy)(x, *w)
            return x.astype(jnp.float32).sum()

        step = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, ws).compile()
        return step.as_text(), step.memory_analysis().temp_size_in_bytes

    def products(text):
        """(in the forward, recomputed) [t, ffn] results of a
        `convolution` read straight from x (a backward one reads dy)."""
        names = re.findall(
            r"= \w+\[%d,%d\]\S* convolution\(.*op_name=\"([^\"]+)\""
            % (t, ffn), text)
        again = [n for n in names if "rematted_computation" in n]
        forward = [n for n in names if "transpose(" not in n]
        return len(forward) - len(again), len(again)

    text, temporaries = compiled(lambda v: checkpoint_name(v, CF.MUL_OUT))
    bare_text, bare_temporaries = compiled(lambda v: v)
    assert products(text) == (2 * layers, 0)
    # (the last layer's second forward is its first over again, and XLA
    # makes the two one)
    assert products(bare_text)[1] >= 2 * (layers - 1)
    named = layers * 2 * t * ffn * 2
    assert bare_temporaries < temporaries <= bare_temporaries + named + 2**20
    computations = re.split(r"\n(?=(?:ENTRY )?%[\w.-]+ \()", text)
    rounded = [c for c in computations if re.search(
        r"ROOT \S+ = bf16\[%d,%d\]\S* reduce-precision\(" % (t, ffn), c)]
    # (the first layer's two: the last layer's backward follows its
    # forward at once, and XLA drops the op there)
    assert rounded and len(rounded) == text.count(" reduce-precision(")
    assert all(" convolution(" in c for c in rounded)
    assert "reduce-precision" not in bare_text


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
