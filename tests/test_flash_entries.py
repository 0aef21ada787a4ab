"""Pallas flash-attention kernel parity (ops/flash_attention.py): the
entries ([B, H, T, D] and the projections' own [B, T, H*D]), the
Program op and the lowering counter. One file of the kernel family's
seven: the walk and the block rule are in tests/test_flash_walk.py, the
backwards in test_flash_backward.py and _backward_streamed.py, the mask
forms in test_flash_masks.py, test_flash_window.py and
test_flash_diff.py, and tests/flash_test.py holds what they share.

The kernel runs here in interpret mode: the same kernel body, traced to
XLA ops for the CPU. It keeps the dtypes the body asks for (float32
inputs stay float32 matmul operands, bfloat16 inputs stay bfloat16 and
p / ds are rounded to bfloat16 before their matmuls, every dot
accumulating in float32), so float32 cases agree with dense math to
rounding and the loose float32 tolerances are headroom, not need; what
it cannot see is the chip's compiler (tests/test_tpu_compile_flash.py) and
the chip's own parity (PERF.md section 6 records it). The dense jnp
formulation is the reference (it equals the composed matmul+softmax ops
the models otherwise emit)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import flash_attention as FA
from flash_test import (_assert_close, _bthd_inputs, _dense,
                        _dense_block_causal, _dense_lse, _f32, _gqa_inputs,
                        _host32, _qkv, _with_grads)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv()
    ref = _dense(q, k, v, causal, 64 ** -0.5)
    got = FA.flash_attention(q, k, v, causal=causal, force="interpret",
                             block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-3, rtol=2e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_dense(causal):
    q, k, v = _qkv(b=1, h=2, t=128, d=64, seed=1)

    def loss(att):
        def f(q, k, v):
            return (att(q, k, v) ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_ref = loss(lambda q, k, v: _dense(q, k, v, causal, 64 ** -0.5))
    g_fa = loss(lambda q, k, v: FA.flash_attention(
        q, k, v, causal=causal, force="interpret",
        block_q=128, block_k=128))
    for name, a, b in zip("qkv", g_ref, g_fa):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 5e-3, (name, err)


def test_uneven_blocks_fall_back_to_dense():
    # T=96 not divisible by the kernel blocks -> auto path must pick dense
    q, k, v = _qkv(t=96)
    out = FA.flash_attention(q, k, v, causal=True)
    ref = FA._dense(q, k, v, True, 64 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_cpu_auto_path_is_dense():
    # on the CPU test platform the auto path must not trace the kernel
    q, k, v = _qkv(t=256)
    out = FA.flash_attention(q, k, v, causal=False)
    ref = FA._dense(q, k, v, False, 64 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_sp_attention_op_routes_through_dispatcher():
    # the registered sp_attention op (off-mesh) must equal the dense math
    import paddle_tpu as fluid
    rng = np.random.RandomState(0)
    q = rng.randn(1, 2, 64, 16).astype(np.float32)
    qv = fluid.layers.data("q", [2, 64, 16])
    kv = fluid.layers.data("k", [2, 64, 16])
    vv = fluid.layers.data("v", [2, 64, 16])
    out = fluid.layers.sequence_parallel_attention(qv, kv, vv, causal=True)
    exe = fluid.Executor(fluid.CPUPlace())
    got, = exe.run(feed={"q": q, "k": q, "v": q}, fetch_list=[out])
    ref = FA._dense(jnp.asarray(q), jnp.asarray(q), jnp.asarray(q), True,
                    16 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_packed_lm_uses_fused_attention():
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        T.transformer_lm(vocab_size=64, max_len=32, n_layer=1, n_head=2,
                         d_model=32, d_inner=64, packed=True)
    ops = [op.type for op in prog.global_block().ops]
    assert "sp_attention" in ops
    prog2 = fluid.Program()
    with fluid.program_guard(prog2, fluid.Program()):
        T.transformer_lm(vocab_size=64, max_len=32, n_layer=1, n_head=2,
                         d_model=32, d_inner=64, packed=False)
    assert "sp_attention" not in [op.type
                                  for op in prog2.global_block().ops]


def test_composed_fallback_keeps_causal_mask():
    # causal + dropout forces the composed branch, which must STILL mask
    # the future (review regression: silently dropped causal)
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T
    rng = np.random.RandomState(0)
    b, t, dm, h = 2, 16, 32, 2
    x = rng.randn(b, t, dm).astype(np.float32) * 0.3
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        xv = fluid.layers.data("x", [t, dm])
        # a (zero) bias forces the composed branch while keeping the op
        # deterministic; causality must still hold: changing FUTURE inputs
        # must not affect earlier outputs
        zero_bias = fluid.layers.assign(
            np.zeros((1, h, t, t), np.float32))
        out = T.multi_head_attention(xv, xv, xv, zero_bias, dm // h,
                                     dm // h, dm, n_head=h, causal=True)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            o1, = exe.run(prog, feed={"x": x}, fetch_list=[out])
            x2 = x.copy()
            x2[:, -1, :] += 100.0
            o2, = exe.run(prog, feed={"x": x2}, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(o1)[:, :-1], np.asarray(o2)[:, :-1],
                               atol=1e-4)


def test_packed_encdec_transformer_matches_masked():
    # packed=True (fused causal self-attn, no bias constants) must equal
    # packed=False under all-ones masks — same math, different route
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    def build(packed, seed=11):
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup):
            prog.random_seed = seed
            cost, _ = T.transformer(
                src_vocab_size=32, trg_vocab_size=32, max_len=8,
                n_layer=1, n_head=2, d_model=16, d_inner=32,
                packed=packed)
            scope = fluid.Scope()
            exe = fluid.Executor(fluid.CPUPlace())
            with fluid.scope_guard(scope):
                exe.run(startup)
            return prog, cost, scope, exe

    p1, c1, s1, e1 = build(False)
    p2, c2, s2, e2 = build(True)
    # identical params
    for v in p1.global_block().all_parameters():
        s2.set(v.name, np.array(np.asarray(s1.find_var(v.name))))

    rng = np.random.RandomState(0)
    b, t = 2, 8
    pos = np.tile(np.arange(t, dtype=np.int64), (b, 1))
    ones = np.ones((b, t), np.float32)
    feeds = {"src_word": rng.randint(3, 32, (b, t)).astype(np.int64),
             "src_pos": pos, "src_mask": ones,
             "trg_word": rng.randint(3, 32, (b, t)).astype(np.int64),
             "trg_pos": pos, "trg_mask": ones,
             "lbl_word": rng.randint(3, 32, (b, t)).astype(np.int64)}
    with fluid.scope_guard(s1):
        l1, = e1.run(p1, feed=feeds, fetch_list=[c1])
    with fluid.scope_guard(s2):
        l2, = e2.run(p2, feed=feeds, fetch_list=[c2])
    np.testing.assert_allclose(float(np.asarray(l1)),
                               float(np.asarray(l2)), rtol=1e-5)
    # and sp_attention really is in the packed program
    assert "sp_attention" in [op.type for op in p2.global_block().ops]
    assert "sp_attention" not in [op.type
                                  for op in p1.global_block().ops]


def test_bwd_vmem_clamp_keeps_divisibility():
    """The d>128 backward block clamp must shrink to a DIVISOR of T: at
    T=768, d=192 the clamp (512 -> 384) still covers every query row —
    gradients match dense (a non-divisor 512 would silently drop rows
    512-767 from dq/dk/dv)."""
    q, k, v = _qkv(b=1, h=1, t=768, d=192, seed=4)

    def grads(att):
        def f(q, k, v):
            return (att(q, k, v) ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_ref = grads(lambda q, k, v: _dense(q, k, v, True, 192 ** -0.5))
    g_fa = grads(lambda q, k, v: FA.flash_attention(
        q, k, v, causal=True, force="interpret"))
    for name, a, b in zip("qkv", g_ref, g_fa):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 5e-3, (name, err)


# -- the projections' own layout (PR 29) -------------------------------------
# (H, D) -> heads to a block, lanes of a block: two heads of 64 to a
# 128-lane block; one head of 128, nothing to separate; four heads of
# 32; and heads that fill no whole lane tile (three of 64: 128 / 64
# does not divide 3), all of H*D as one block.
_LAYOUTS = [pytest.param(16, 64, 2, id="H16-D64-g2"),
            pytest.param(4, 128, 1, id="H4-D128-g1"),
            pytest.param(4, 32, 4, id="H4-D32-g4"),
            pytest.param(3, 64, 3, id="H3-D64-whole_width")]


@pytest.mark.parametrize("h, d, g", _LAYOUTS)
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("block", [None, 128], ids=["one_block", "streamed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_bthd_entry_matches_dense(dtype, block, causal, h, d, g):
    """out, lse, dq, dk, dv of the kernels reading [B, T, H*D] as it is,
    g heads to a block, with a NON-ZERO lse cotangent, against dense
    float32 math on [B, H, T, D]."""
    assert FA.heads_per_block(h, d) == g
    q, k, v, dy, dlse = _bthd_inputs(h, d, dtype)
    scale = d ** -0.5
    kw = dict(causal=causal, force="interpret", block_q=block,
              block_k=block)
    if block is None:     # all of T is one block at these sizes
        assert FA._resolve_path(FA.heads_first(q, h), None, None, None,
                                "interpret")[2:] == (256, 256)

    def weigh(outs):
        o, lse = outs
        return (_f32(o) * _f32(dy)).sum() + (lse * dlse).sum()

    def ref(q, k, v):
        o, lse = _dense_lse(*(FA.heads_first(x, h) for x in (q, k, v)),
                            causal, scale)
        return FA.heads_last(o), lse

    # each side's results and gradients as ONE program, the output
    # alone beside the kernels'
    (o_ref, lse_ref), g_ref = jax.jit(_with_grads(ref, weigh))(
        *_host32(q, k, v))
    both = _with_grads(lambda q, k, v: FA.flash_bthd_lse(q, k, v, h, **kw),
                       weigh)
    ((o, lse), grads), o_alone = jax.jit(lambda q, k, v: (
        both(q, k, v), FA.flash_bthd(q, k, v, h, **kw)))(q, k, v)
    assert o.shape == q.shape and o.dtype == dtype
    assert lse.shape == (1, h, 256) and lse.dtype == jnp.float32
    # the tolerances of test_tiled_walk_matches_dense
    tol_o, tol_g = (2e-3, 5e-3) if dtype == jnp.float32 else (1e-2, 2e-2)
    _assert_close("out", o, o_ref, tol_o)
    _assert_close("lse", lse, lse_ref, tol_o)
    # the output alone, through the other custom_vjp, is the same bits
    assert np.array_equal(np.asarray(o), np.asarray(o_alone))
    for name, a, b in zip(("dq", "dk", "dv"), grads, g_ref):
        assert a.shape == q.shape and a.dtype == dtype
        _assert_close(name, a, b, tol_g)


@pytest.mark.parametrize("h, hkv, d, g, path", [
    pytest.param(8, 2, 64, 2, "interpret", id="H8-Hkv2-D64-g2-groups_of_4"),
    pytest.param(8, 4, 64, 2, "interpret", id="H8-Hkv4-D64-g2-groups_of_2"),
    pytest.param(4, 1, 32, 4, "interpret", id="H4-Hkv1-D32-g4-one_group"),
    pytest.param(6, 2, 64, 2, "dense", id="H6-Hkv2-D64-g2-groups_of_3")])
@pytest.mark.parametrize("block", [None, 128], ids=["one_block", "streamed"])
def test_grouped_heads_several_to_a_block_take_the_kernels(block, h, hkv, d,
                                                           g, path):
    """ISSUE 49: where the g > 1 heads of a block divide a group of
    query heads, all of them read ONE key/value head, and the kernels
    run on k and v spread under the query heads' lanes: out, lse, dq,
    and dk, dv in k's own shape against dense float32 math, and the
    lowering's labels (`path`, `heads_per_block`, `kv_groups`). A
    group that g does not divide is still dense math, and says so."""
    assert FA.heads_per_block(h, d) == g
    q, k, v, dy, dlse = _gqa_inputs(h, hkv, d, 256, jnp.float32)
    kw = dict(causal=True, force="interpret", n_kv_head=hkv, block_q=block,
              block_k=block)
    labels = dict(path=path, entry="bthd", heads_per_block=str(g),
                  backward="none" if path == "dense" else
                  "fused" if block is None else "fused_streamed",
                  mask="causal", kv_groups=str(h // hkv), key_width=str(d),
                  value_width=str(d), second_part="none", window="0")
    was = FA._LOWERINGS.value(**labels)

    def weigh(outs):
        o, lse = outs
        return (o * dy).sum() + (lse * dlse).sum()

    def ref(q, k, v):
        o, lse = _dense_lse(FA.heads_first(q, h), FA.heads_first(k, hkv),
                            FA.heads_first(v, hkv), True, d ** -0.5)
        return FA.heads_last(o), lse

    (o_ref, lse_ref), g_ref = jax.jit(_with_grads(ref, weigh))(q, k, v)
    (o, lse), grads = jax.jit(_with_grads(
        lambda q, k, v: FA.flash_bthd_lse(q, k, v, h, **kw), weigh))(q, k, v)
    assert FA._LOWERINGS.value(**labels) == was + 1
    _assert_close("out", o, o_ref, 2e-3)
    _assert_close("lse", lse, lse_ref, 2e-3)
    for name, a, b, x in zip(("dq", "dk", "dv"), grads, g_ref, (q, k, v)):
        assert a.shape == x.shape and a.dtype == x.dtype
        _assert_close(name, a, b, 5e-3)


@pytest.mark.parametrize("with_lse", [False, True], ids=["out", "out_lse"])
def test_bhtd_wrappers_equal_the_bthd_entry_bit_for_bit(with_lse):
    """flash_attention / flash_attention_lse on [B, H, T, D] are the new
    entry between two transposes: the same bits, forward and backward."""
    h, d = 4, 64
    q, k, v, dy, dlse = _bthd_inputs(h, d, jnp.bfloat16, b=2)
    kw = dict(causal=True, force="interpret")

    def loss(att):
        def f(q, k, v):
            o, lse = att(q, k, v)
            extra = (lse * dlse).sum() if with_lse else 0.0
            return (_f32(o) * _f32(dy)).sum() + extra
        return f

    def new(q, k, v):
        if with_lse:
            return FA.flash_bthd_lse(q, k, v, h, **kw)
        return FA.flash_bthd(q, k, v, h, **kw), None

    def old(q, k, v):      # the same [B, T, H*D] operands, heads first
        args = [FA.heads_first(x, h) for x in (q, k, v)]
        if with_lse:
            o, lse = FA.flash_attention_lse(*args, **kw)
            return FA.heads_last(o), lse
        return FA.heads_last(FA.flash_attention(*args, **kw)), None

    for a, b in zip(new(q, k, v), old(q, k, v)):
        assert (a is None and b is None) or jnp.array_equal(a, b)
    for a, b in zip(jax.grad(loss(new), (0, 1, 2))(q, k, v),
                    jax.grad(loss(old), (0, 1, 2))(q, k, v)):
        assert jnp.array_equal(a, b)


def _fused_lm(packed, n_layer=2, seed=13):
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        prog.random_seed = seed
        cost, _ = T.transformer_lm(vocab_size=48, max_len=16,
                                   n_layer=n_layer, n_head=4, d_model=32,
                                   d_inner=64, packed=packed)
        grads = fluid.backward.append_backward(cost)
    return prog, startup, cost, grads


def test_fused_lm_hands_the_projections_straight_to_sp_attention():
    """No reshape or transpose between the q/k/v `mul` ops and
    sp_attention, nor between it and the output projection; parameter
    names are those of the composed branch; loss and every gradient
    equal the composed branch's."""
    import paddle_tpu as fluid
    prog, startup, cost, grads = _fused_lm(True)
    ops = prog.global_block().ops
    producer = {name: op for op in ops for name in op.output_names}
    consumers = {}
    for op in ops:
        for name in op.input_names:
            consumers.setdefault(name, []).append(op.type)
    fused = [op for op in ops if op.type == "sp_attention"]
    assert len(fused) == 2
    for op in fused:
        assert op.attr("n_head") == 4
        for slot in ("Q", "K", "V"):
            assert producer[op.input(slot)[0]].type == "mul"
        assert "mul" in consumers[op.output("Out")[0]]
    forward = [op.type for op in ops[:ops.index(fused[-1])]]
    assert "transpose" not in forward
    # the only reshape before the last layer's attention is none of
    # attention's: 7 ops a layer's attention had (3 reshape + 3
    # transpose in, transpose + reshape out) are gone
    assert forward.count("reshape") == 0

    prog2, startup2, cost2, grads2 = _fused_lm(False)
    names = [p.name for p in prog.global_block().all_parameters()]
    assert names == [p.name for p in prog2.global_block().all_parameters()]
    assert [tuple(p.shape) for p in prog.global_block().all_parameters()] \
        == [tuple(p.shape) for p in prog2.global_block().all_parameters()]

    rng = np.random.RandomState(0)
    b, t = 2, 16
    feeds = {"src": rng.randint(1, 48, (b, t)).astype(np.int64),
             "pos": np.tile(np.arange(t, dtype=np.int64), (b, 1)),
             "mask": np.ones((b, t), np.float32),
             "label": rng.randint(1, 48, (b, t)).astype(np.int64)}
    exe = fluid.Executor(fluid.CPUPlace())
    s1, s2 = fluid.Scope(), fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup)
        r1 = exe.run(prog, feed=feeds,
                     fetch_list=[cost] + [g for _, g in grads])
    for name in names:
        s2.set(name, np.array(np.asarray(s1.find_var(name))))
    with fluid.scope_guard(s2):
        r2 = exe.run(prog2, feed=feeds,
                     fetch_list=[cost2] + [g for _, g in grads2])
    assert [p.name for p, _ in grads] == [p.name for p, _ in grads2]
    np.testing.assert_allclose(float(np.asarray(r1[0])),
                               float(np.asarray(r2[0])), rtol=1e-5)
    for (p, _), a, b_ in zip(grads, r1[1:], r2[1:]):
        _assert_close(p.name, jnp.asarray(a), jnp.asarray(b_), 5e-3)


def test_sp_attention_of_rank_3_and_of_rank_4_agree():
    """The op observes the rank: [B, T, H*dk] with n_head and
    [B, H, T, dk] are the same attention."""
    import paddle_tpu as fluid
    h, t, dk = 4, 64, 16
    rng = np.random.RandomState(1)
    x3 = [rng.randn(2, t, h * dk).astype(np.float32) for _ in range(3)]
    x4 = [a.reshape(2, t, h, dk).transpose(0, 2, 1, 3) for a in x3]
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        v3 = [fluid.layers.data(n, [t, h * dk]) for n in ("q3", "k3", "v3")]
        v4 = [fluid.layers.data(n, [h, t, dk]) for n in ("q4", "k4", "v4")]
        o3 = fluid.layers.sequence_parallel_attention(*v3, causal=True,
                                                      n_head=h)
        o4 = fluid.layers.sequence_parallel_attention(*v4, causal=True)
        assert tuple(o3.shape[1:]) == (t, h * dk)
        with pytest.raises(ValueError, match="n_head"):
            fluid.layers.sequence_parallel_attention(*v3, causal=True)
        with pytest.raises(ValueError, match="no n_head"):
            fluid.layers.sequence_parallel_attention(*v4, n_head=h)
    feed = dict(zip(("q3", "k3", "v3", "q4", "k4", "v4"), x3 + x4))
    got3, got4 = fluid.Executor(fluid.CPUPlace()).run(
        prog, feed=feed, fetch_list=[o3, o4])
    np.testing.assert_allclose(
        np.asarray(got3).reshape(2, t, h, dk).transpose(0, 2, 1, 3),
        np.asarray(got4), atol=1e-6)
    ref = FA._dense(*(jnp.asarray(a) for a in x4), True, dk ** -0.5)
    np.testing.assert_allclose(np.asarray(got4), np.asarray(ref), atol=1e-5)


def test_the_streamed_kernels_bound_counts_what_stays_in_vmem():
    """The byte bound is read off the shapes: float32 operands keep a
    float32 output block (12 bytes a resident element for bf16's 8),
    the own-block form's rows are both halves, and the scoped VMEM the
    kernel asks for covers what is resident and stays under a v5e
    core's 128 MiB at the largest shape the bound lets through."""
    assert FA._backward_of(32768, 128, 1024, 1024, itemsize=2) \
        == "fused_streamed"
    assert FA._backward_of(32768, 128, 1024, 1024, itemsize=4) \
        == "two_kernels"
    own = (4, FA._OWN)
    assert FA._backward_of(16384, 128, 1024, 1024, own) == "fused_streamed"
    assert FA._backward_of(32768, 128, 1024, 1024, own) == "two_kernels"
    assert FA._backward_of(256, 128, 256, 256, own) == "fused_streamed"
    for t, itemsize in ((32768, 2), (16384, 4), (4096, 2)):
        asked = FA._one_kernel_vmem_bytes(t, 128, 1024, 1024, 1, itemsize, 4,
                                        4, 2)
        assert t * 128 * (4 + 2 * itemsize) < asked <= 120 * 1024 * 1024


def test_lowering_counter_says_which_path_engaged():
    """`ptpu_flash_lowerings_total{path, entry, heads_per_block,
    backward, mask, kv_groups}`: one count a lowering of the fused model's attention (the
    forward's trace; none a step), `dense` off the chip, where no
    backward kernel will run; the [B, H, T, D] wrappers count as `bhtd`;
    `backward` says which backward the lowering's gradient takes: the
    one fused kernel where a block holds all of T, the ONE streamed
    kernel where dq for all rows fits its byte bound, else the two."""
    import paddle_tpu as fluid
    n_layer = 3
    prog, startup, cost, _ = _fused_lm(True, n_layer=n_layer)
    rng = np.random.RandomState(0)
    feeds = {"src": rng.randint(1, 48, (2, 16)).astype(np.int64),
             "pos": np.tile(np.arange(16, dtype=np.int64), (2, 1)),
             "mask": np.ones((2, 16), np.float32),
             "label": rng.randint(1, 48, (2, 16)).astype(np.int64)}
    count = FA._LOWERINGS
    # d_model 32 over 4 heads: D 8, sixteen heads would fill 128 lanes,
    # four do not: all of H*D as one block, four heads to it
    labels = dict(path="dense", entry="bthd", heads_per_block="4",
                  backward="none", mask="causal", kv_groups="1",
                  key_width="8", value_width="8", second_part="none", window="0")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        before = count.value(**labels)
        exe.run(prog, feed=feeds, fetch_list=[cost])
        lowered = count.value(**labels) - before
        exe.run(prog, feed=feeds, fetch_list=[cost])     # a cached step
        assert count.value(**labels) - before == lowered
    assert lowered == n_layer
    q, k, v = _qkv(b=1, h=2, t=256, d=64)
    for block, bound, backward in ((None, None, "fused"),
                                   (128, None, "fused_streamed"),
                                   (128, 0, "two_kernels")):
        labels = dict(path="interpret", entry="bhtd", heads_per_block="2",
                      backward=backward, mask="causal", kv_groups="1",
                      key_width="64", value_width="64", second_part="none", window="0")
        was = count.value(**labels)
        with pytest.MonkeyPatch.context() as patch:
            if bound is not None:
                patch.setattr(FA, "_RESIDENT_DQ_BYTES", bound)
            FA.flash_attention(q, k, v, causal=True, force="interpret",
                               block_q=block, block_k=block)
        assert count.value(**labels) == was + 1
    assert "fused_streamed" in count.help
    assert "ptpu_flash_lowerings_total" in \
        fluid.monitor.metrics.registry().render_prometheus()


def test_what_the_kernels_cannot_take_goes_dense():
    """A group of query heads shares a block of k where a block is one
    head or its heads divide the group (ISSUE 49), and a mask's block
    must divide the tiles: anything else is dense math, also when a
    caller forces the kernel; the counter's `mask` and `kv_groups`
    labels say what was asked. Two heads of 64 to a block in groups of
    THREE: a block would read two key/value heads."""
    count = FA._LOWERINGS
    q, k, v, _, _ = _gqa_inputs(6, 2, 64, 256, jnp.float32)
    labels = dict(path="dense", entry="bthd", heads_per_block="2",
                  backward="none", mask="block_causal_strict", kv_groups="3",
                  key_width="64", value_width="64", second_part="none", window="0")
    was = count.value(**labels)
    o = FA.flash_bthd(q, k, v, 6, causal=True, force="interpret",
                      n_kv_head=2, mask_block=4, strict=True)
    assert count.value(**labels) == was + 1
    o_ref, _, seen = _dense_block_causal(q, k, v, 6, 2, 4, True)
    _assert_close("out", jnp.where(seen[None, :, None], o, 0), o_ref, 1e-5)
    q, k, v, _, _ = _gqa_inputs(4, 2, 128, 256, jnp.float32)
    labels = dict(path="interpret", entry="bthd", heads_per_block="1",
                  backward="fused", mask="block_causal", kv_groups="2",
                  key_width="128", value_width="128", second_part="none", window="0")
    was = count.value(**labels)
    FA.flash_bthd(q, k, v, 4, causal=True, force="interpret", n_kv_head=2,
                  mask_block=32)
    assert count.value(**labels) == was + 1
    with pytest.raises(ValueError):
        FA.flash_bthd(q, k, v, 4, causal=True, mask_block=6, n_kv_head=2)
    with pytest.raises(ValueError):
        FA.flash_bthd(q, k, v, 4, n_kv_head=3)
