"""The kernels of the residual streams and the scan compiled for a
described v5e (tests/tpu_compile_test.py says how and why): the
selective scan's chunked pair (``ops/selective_scan.py``) and the
hyper-connections' four (``ops/hyper_connection.py``), each at its
cell's shape; and the gated delta rule (``ops/delta_rule.py``): its
kernel pair, the path a v5e takes at the cell's shape, and the
``jax.numpy`` chunk walk that every other device and shape takes, whose
lowering and temporaries are checked here where no chip is.
"""

import pytest

from tpu_compile_test import _compiled_text, chip, topo  # noqa: F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


# ISSUE 40: the selective scan's chunked kernel pair at the cell
# `phi4flash_train_T8k`'s shape (one packed 8,192-token sequence, 5,120
# channels of 16 states, bf16 operands).
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


# ISSUEs 53 and 54: the gated delta rule at the cell
# `olmohybrid_train_T8k`'s shape (one packed 8,192-token sequence, 15
# heads, keys of 96, values of 192, bf16 operands, float32 gates).
def _delta_rule_compiled(chip, direction, path):
    from paddle_tpu.ops.delta_rule import gated_delta_rule
    b, t, h, d_k, d_v = 1, 8192, 15, 96, 192
    sd = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip)
    avals = (sd((b, t, h, d_k)), sd((b, t, h, d_k)), sd((b, t, h, d_v)),
             sd((b, t, h), jnp.float32), sd((b, t, h), jnp.float32))
    # (the described chip is not the default backend: the path a v5e
    # takes by itself is pinned here, as the scan's is above)
    rule = lambda *a: gated_delta_rule(*a, force=path)
    loss = lambda *a: rule(*a).astype(jnp.float32).sum()
    fn = rule if direction == "fwd" \
        else jax.grad(loss, argnums=tuple(range(5)))
    return jax.jit(fn).lower(*avals).compile(), (b, t, h, d_k, d_v)


def _float_sizes(text):
    """{(dtype, the dimensions other than 1, sorted)} of every f32 and
    bf16 value in the compiled text."""
    import re
    return {(kind, tuple(sorted(int(x) for x in dims.split(",")
                                if int(x) > 1)))
            for kind, dims in re.findall(r"\b(f32|bf16)\[([\d,]+)\]", text)}


@pytest.mark.parametrize("direction", ["fwd", "grad"])
def test_gated_delta_rule_compiles_for_v5e(chip, direction):
    """q and k [1, 8192, 15, 96], v [1, 8192, 15, 192] on the path a
    v5e takes: Mosaic accepts ``delta_rule_fwd`` (and, for the gradient
    of all five inputs, ``delta_rule_bwd``: ONE kernel a direction) at
    widths that are no whole lane tiles, inside the compiler's own
    16 MiB of VMEM (the kernels ask for no limit of their own); the
    chunks are walked by the kernels' grids, so no while loop is left;
    the chunks' starting states are float32, [15, 128, 96, 192] at the
    kernels' chunks of 64 rows (141 MB), the largest float32 value
    there is and the largest of all; and nothing of a chunk's [C, C]
    parts (the decays, the Gram products, T_) reaches HBM: no float32
    value of [15, 128, 64, 64]."""
    import math
    from paddle_tpu.ops import delta_rule as DR
    compiled, (b, t, h, d_k, d_v) = _delta_rule_compiled(
        chip, direction, "pallas")
    text = compiled.as_text()
    names = ["delta_rule_fwd"] + (["delta_rule_bwd"]
                                  if direction == "grad" else [])
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert "%" + name + "." in text or "%" + name + " " in text
    assert "vmem_limit_bytes" not in text
    assert " while(" not in text
    c = DR.KERNEL_CHUNK
    sizes = _float_sizes(text)
    states = tuple(sorted((h, t // c, d_k, d_v)))
    assert ("f32", states) in sizes and ("bf16", states) not in sizes
    assert max(math.prod(dims) for _, dims in sizes) == math.prod(states)
    assert ("f32", tuple(sorted((h, t // c, c, c)))) not in sizes
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (0.5 if direction == "fwd" else 0.75) * 2 ** 30, temp


@pytest.mark.parametrize("direction", ["fwd", "grad"])
def test_gated_delta_rules_jax_numpy_walk_compiles_for_v5e(chip, direction):
    """The same shapes on the path every other device and shape takes
    (``force="chunked"``): the walk is ONE
    while loop of 128 trips forward (one more, reversed, for the
    gradient), never 8,192; no custom call; the state is float32 (no
    bf16 value as large as the chunk states); the largest value is the
    chunk states, [128, 1, 15, 96, 192] float32 (141 MB), and the
    temporaries stay under 0.75 GiB forward (0.47 today) and 3 GiB
    with the gradient of all five inputs (2.3)."""
    import math
    compiled, (b, t, h, d_k, d_v) = _delta_rule_compiled(
        chip, direction, "chunked")
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    # the walk's loops, each over the 128 chunks' stacked values
    assert text.count(" while(") == (1 if direction == "fwd" else 2)
    assert "f32[%d,%d,%d,%d,%d]" % (t // 64, b, h, d_k, d_v) in text
    states = t // 64 * h * d_k * d_v
    sizes = {(kind, math.prod(dims)) for kind, dims in _float_sizes(text)}
    assert max(size for _, size in sizes) == states
    assert max(size for kind, size in sizes if kind == "bf16") < states
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (0.75 if direction == "fwd" else 3) * 2 ** 30, temp


# ISSUE 58: the embedding's gradient alone (ops/embedding_grad.py) at the
# two cells whose step it shortens, ids [T] into a float32 table [V, d].
@pytest.mark.parametrize("shape", [(16384, 37984, 2560), (8192, 12544, 3840)],
                         ids=["smallthinker_train_T16k",
                              "olmohybrid_train_T8k"])
def test_embedding_grad_compiles_for_v5e(chip, shape):
    """The gradient of `take_rows` on the path a v5e takes: Mosaic
    accepts the kernel (a row's read, add and write at a DYNAMIC sublane
    of both blocks, which interpret mode does not judge), the compiled
    program holds it by the name a device trace will show, XLA's
    row-by-row `scatter` is gone from it, and the items' index math is
    no `while` of gathers."""
    import re
    from paddle_tpu.ops import embedding_grad as eg
    t, vocab, d = shape
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=chip)

    def grad(w, ids, dy):
        return jax.vjp(lambda w: eg.take_rows(w, ids, force="pallas"),
                       w)[1](dy)[0]

    text = _compiled_text(grad, sd((vocab, d), jnp.float32),
                          sd((t,), jnp.int32), sd((t, d), jnp.float32))
    assert text.count("tpu_custom_call") == 1
    assert "%embedding_grad_rows." in text or "%embedding_grad_rows " in text
    assert not re.search(r"f32\[%d,%d\]\S* scatter\(" % (vocab, d), text)
    assert " scatter(" not in text and " while(" not in text


# ISSUE 65: the causal depthwise convolution + SiLU in front of a scan
# or a delta rule (ops/ssm_conv.py) at the widths of the four cells that
# run it, one sequence of 8,192 rows under 4 taps, bfloat16.
@pytest.mark.parametrize("c,biased,dtype", [
    (4096, True, jnp.bfloat16), (128, True, jnp.bfloat16),
    (1024, False, jnp.bfloat16), (5120, False, jnp.bfloat16),
    (1440, False, jnp.bfloat16), (2880, False, jnp.bfloat16),
    (2880, False, jnp.float32)],
    ids=["granites_x", "granites_b_or_c", "nemotrons_b_or_c", "phi4flashs",
         "olmo_hybrids_q_or_k", "olmo_hybrids_v", "olmo_hybrids_v_float32"])
def test_ssm_conv_kernels_compile_for_v5e(chip, c, biased, dtype):
    """The path a v5e takes: ONE kernel a direction, by the names a
    device trace will show; sublane shifts of float32 values at offsets
    that are not whole tiles, a 16-row block of x before a tile and, at
    1,440 and 2,880 channels, a last block of 512 lanes that is partly
    outside the array (of x, dy, w and the sums alike), which only the
    chip's compiler judges; dw and dbias come back as partial sums by
    sublane, [B, K + 1, 8, C]."""
    from paddle_tpu.ops import ssm_conv
    t, k = 8192, 4
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=chip)
    avals = (sd((1, t, c), dtype), sd((k, c), jnp.float32)) + (
        (sd((c,), jnp.float32),) if biased else ())

    def loss(*a):
        return ssm_conv.conv_silu(*a).astype(jnp.float32).sum()

    text = _compiled_text(
        jax.value_and_grad(loss, argnums=tuple(range(len(avals)))), *avals)
    assert text.count("tpu_custom_call") == 2
    for name in ("ssm_conv_fwd", "ssm_conv_bwd"):
        assert "%" + name + "." in text or "%" + name + " " in text
    assert "f32[1,%d,8,%d]" % (k + biased, c) in text
