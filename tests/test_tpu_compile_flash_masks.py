"""The flash kernels' mask forms and score forms compiled for a
described v5e (tests/tpu_compile_test.py says how and why), each at its
cell's real width, which is their point: block diffusion's
(``sdar_train_bd4k``), latent attention's two-part score
(``xing4_train_T4k``), the window's (``trinity_train_T16k``), a group of seven query heads
(``smallthinker_train_T16k``) and differential attention's
(``phi4flash_train_T8k``). The plain entries
are tests/test_tpu_compile_flash.py's.
"""

import pytest

from tpu_compile_test import _compiled_text, chip, topo  # noqa: F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops import flash_attention as FA  # noqa: E402
from paddle_tpu.ops.flash_attention import flash_bthd  # noqa: E402

_ONE, _TWO = ["flash_bwd"], ["flash_bwd_dq", "flash_bwd_dkv"]


# ISSUE 32: the block-diffusion cell's shapes. 32 query heads of 128
# reading 4 key/value heads at T 4096 (streamed 1024-blocks, ONE backward
# kernel since ISSUE 39), under the three forms of the block-granular mask;
# and the attention of the whole objective (since ISSUE 37 the third form,
# one call of each kernel over [noised; clean] rows and nothing outside
# them).
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


# --------------------------------------------------------------------------
# ISSUEs 34 and 56: latent attention's score of two parts at the cells'
# shapes, streamed: q_nope / k_nope / v [1, T, 32 x 128], q_pe
# [1, T, 32 x 64] and ONE k_pe [1, T, 64], T 4096 (xing4_train_T4k) and
# 8192 (joyai_train_T8k).
@pytest.mark.parametrize("direction, t, backward", [
    ("fwd", 4096, None), ("bwd", 4096, "fused_streamed"),
    ("bwd", 8192, "fused_streamed"), ("bwd", 4096, "two_kernels")],
    ids=["fwd_T4k", "bwd_T4k", "bwd_T8k", "bwd_T4k_over_the_bound"])
def test_two_part_score_compiles_for_v5e(chip, monkeypatch, direction, t,
                                         backward):
    """The streamed kernels with a second score part, under their own
    names: the forward, and the ONE backward kernel flash_bwd, whose
    scoped VMEM (_one_kernel_vmem_bytes: both heads' dq of a pair and
    dq_pe for all of T, 25 MB of the 48 asked for at T 8192) the
    compiler accepts; with no shape within the byte bound, flash_bwd_dq
    + flash_bwd_dkv as before. Nothing of k_pe's size times the heads
    exists (the shared key is repeated to ONE 128-lane tile,
    [1, T, 128]), no operand is padded to 256 lanes a head, and dk_pe's
    sum over the heads is made in the kernel a pair of heads at a time:
    what lies between the kernels and the gradients is the 16 pairs'
    float32 partials [16, T, 128], their sum, and the fold of that one
    tile."""
    import math
    import re
    b, h, d, d2 = 1, 32, 128, 64
    sds = lambda lanes: jax.ShapeDtypeStruct((b, t, lanes), jnp.bfloat16,
                                             sharding=chip)
    avals = (sds(h * d), sds(h * d), sds(h * d), sds(h * d2), sds(d2))
    if backward == "two_kernels":
        monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)
    count = lambda: sum(
        v for key, v in FA._LOWERINGS.snapshot().items()
        if key[FA._LOWERINGS.label_names.index("backward")] == backward
        and key[FA._LOWERINGS.label_names.index("second_part")] == "shared")
    was = count()

    def fwd(q, k, v, q2, k2):
        return flash_bthd(q, k, v, h, causal=True, force="pallas", q2=q2,
                          k2=k2)

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss,
                                                 argnums=(0, 1, 2, 3, 4))
    text = _compiled_text(fn, *avals)
    names = ["flash_fwd"] + {None: [], "fused_streamed": _ONE,
                             "two_kernels": _TWO}[backward]
    assert text.count("tpu_custom_call") == len(names)
    assert set(re.findall(r"%(flash_\w+?)(?:\.\d+)? = ", text)) == set(names)
    if backward:
        assert count() == was + 1
    sizes = {math.prod(int(x) for x in dims.split(","))
             for dims in re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text)}
    # operands and gradients as they come, the statistics' rows, the
    # one tile of k_pe, a pair's partial of it, and nothing wider
    assert max(sizes) == b * t * h * d
    assert b * t * h * 2 * d not in sizes and b * t * h * (d + d2) not in sizes
    if backward == "fused_streamed":
        assert "f32[%d,%d,128]" % (h // 2, t) in text


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


# ISSUE 46: a group of SEVEN query heads to a key/value head. The cell
# `smallthinker_train_T16k`'s layers: one packed 16,384-token sequence, 28
# query heads of 128 (wider than the stream's 2560) reading 4, a window of
# 4096 on three layers of four and plain causal on the fourth.
@pytest.mark.parametrize("window", [4096, None],
                         ids=["window_4096", "full"])
def test_a_group_of_seven_compiles_for_v5e(chip, window):
    """q [1, 16384, 3584] against k, v [1, 16384, 512], forward and
    backward: the forward and the ONE streamed backward kernel
    (flash_bwd, dq held in VMEM across the key blocks; not the pair
    flash_bwd_dq + flash_bwd_dkv), and no dense path: no [T, T] value,
    the largest buffer the program names is an operand's size."""
    import math
    import re
    b, t, h, hkv, d = 1, 16384, 28, 4, 128
    q = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, t, hkv * d), jnp.bfloat16, sharding=chip)
    labels = dict(kv_groups="7", window=str(window or 0))
    count = lambda **kw: sum(
        v for key, v in FA._LOWERINGS.snapshot().items()
        if all(key[FA._LOWERINGS.label_names.index(k)] == want
               for k, want in {**labels, **kw}.items()))
    # (the counter is the process's: a worker that ran a CPU model of
    # the same groups before this file has counted its dense lowerings)
    was = (count(backward="fused_streamed"), count(path="dense"),
           count(backward="two_kernels"))

    def loss(q, k, v):
        return flash_bthd(q, k, v, h, causal=True, n_kv_head=hkv,
                          force="pallas", window=window
                          ).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert count(backward="fused_streamed") == was[0] + 1
    assert (count(path="dense"), count(backward="two_kernels")) == was[1:]
    assert text.count("tpu_custom_call") == 2
    calls = set(re.findall(r"%(flash_\w+?)(?:\.\d+)? = ", text))
    assert calls == {"flash_fwd", "flash_bwd"}
    size = lambda dims: math.prod(int(x) for x in dims.split(","))
    assert max(size(dims) for dims in re.findall(r"[fb]\w*\[([\d,]+)\]", text)
               ) <= b * t * h * d


# ISSUE 40: differential attention's window layer at the cell
# `phi4flash_train_T8k`'s shape (40 query and 20 key/value heads of 64,
# values of 128, a window of 512).
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
