"""Pallas flash-attention kernel parity (ops/flash_attention.py).

The kernel runs here in interpret mode: the same kernel body, traced to
XLA ops for the CPU. It keeps the dtypes the body asks for (float32
inputs stay float32 matmul operands, bfloat16 inputs stay bfloat16 and
p / ds are rounded to bfloat16 before their matmuls, every dot
accumulating in float32), so float32 cases agree with dense math to
rounding and the loose float32 tolerances are headroom, not need; what
it cannot see is the chip's compiler (tests/test_tpu_compile.py) and
the chip's own parity (PERF.md section 6 records it). The dense jnp
formulation is the reference (it equals the composed matmul+softmax ops
the models otherwise emit)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import flash_attention as FA


def _qkv(b=2, h=3, t=256, d=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, t, d), jnp.float32) * 0.3
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv()
    ref = FA._dense(q, k, v, causal, 64 ** -0.5)
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

    g_ref = loss(lambda q, k, v: FA._dense(q, k, v, causal, 64 ** -0.5))
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


def test_auto_blocks_divide_non_pow2_t():
    """Auto block sizing must pick a DIVISOR of T (largest <= 1024), so
    T=1536 keeps the fused kernel instead of demoting to dense."""
    path, _, bq, bk = FA._resolve_path(
        jnp.zeros((1, 1, 1536, 128)), None, None, None, "interpret")
    assert bq == 768 and bk == 768
    assert 1536 % bq == 0
    # and the kernel at those blocks matches dense
    q, k, v = _qkv(b=1, h=1, t=1536, d=32, seed=3)
    got = FA.flash_attention(q, k, v, causal=True, force="interpret")
    ref = FA._dense(q, k, v, True, 32 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-3, rtol=2e-2)


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

    g_ref = grads(lambda q, k, v: FA._dense(q, k, v, True, 192 ** -0.5))
    g_fa = grads(lambda q, k, v: FA.flash_attention(
        q, k, v, causal=True, force="interpret"))
    for name, a, b in zip("qkv", g_ref, g_fa):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 5e-3, (name, err)


def test_auto_block_degenerate_t_demotes_to_dense(monkeypatch):
    """T with no divisor >= 128 under the auto cap (prime 4099, 2*1031)
    must NOT build a near-T^2 grid of tiny blocks — auto sizing demotes
    to the dense path; explicit block sizes still honor the caller."""
    monkeypatch.setattr(FA, "_on_tpu", lambda x: True)

    def path_for(t, block=None):
        q = jnp.zeros((1, 1, t, 64), jnp.float32)
        return FA._resolve_path(q, None, block, block, None)[0]

    assert path_for(2048) == "pallas"        # sanity: clean T stays fused
    assert path_for(4099) == "dense"         # prime
    assert path_for(2 * 1031) == "dense"     # largest divisor 2
    assert path_for(17 * 127) == "dense"     # largest divisor 127 < 128
    assert path_for(2062, block=1031) == "pallas"  # explicit block wins


# -- the walk inside a major block (PR 25) ----------------------------------
# (T, D, panel target, block_q, block_k, cap on an unmasked panel's
# scores or None for the module's) -> the panel edge it gives
_WALKS = [
    # one major block, the whole of T, cut into 4 panels of 128 on the
    # diagonal: the tiles above it never computed, the mask on the
    # diagonal's tiles only, the forward with nothing to rescale (one
    # key block), dk/dv's panels by key with the scores transposed
    pytest.param(512, 64, 128, 512, 512, None, 128, id="T512-one_block"),
    # 2 x 2 major blocks of two panels: the block below the diagonal in
    # one unmasked panel, the block above it skipped, scratch and the
    # running max carried between grid steps; a scale that is no power
    # of two
    pytest.param(512, 32, 128, 256, 256, None, 128, id="T512-four_blocks"),
    # the same with unmasked panels capped at 128 x 256 scores: the
    # block below the diagonal (and every block of the full case) is
    # cut into two
    pytest.param(512, 64, 128, 256, 256, 128 * 256, 128,
                 id="T512-capped_panels"),
    # unequal blocks cross the diagonal anywhere: one panel masked at
    # the offset the grid step gives
    pytest.param(768, 64, 256, 256, 384, None, 256, id="T768-unequal"),
]


def _walk_inputs(t, d, dtype, seed):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape) * 0.5, jnp.float32)
    q, k, v, dy = (mk(1, 2, t, d).astype(dtype) for _ in range(4))
    return q, k, v, dy, mk(1, 2, t)


def _f32(a):
    return a.astype(jnp.float32)


def _assert_close(name, got, want, tol):
    """Largest error over the largest reference value."""
    err = float(jnp.max(jnp.abs(_f32(got) - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-9)
    assert err < tol, (name, err)


@pytest.mark.parametrize("t, d, tile, bq, bk, scores, edge", _WALKS)
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_tiled_walk_matches_dense(monkeypatch, dtype, causal, t, d, tile,
                                  bq, bk, scores, edge):
    """out, lse, dq, dk, dv of the kernels walking their major blocks
    in panels, against dense float32 math on the same inputs."""
    monkeypatch.setattr(FA, "_TILE", tile)
    if scores:
        monkeypatch.setattr(FA, "_PANEL_SCORES", scores)
    assert FA._tile(bq, tile) == edge
    q, k, v, dy, _ = _walk_inputs(t, d, dtype, seed=5)
    scale = d ** -0.5
    kw = dict(causal=causal, force="interpret", block_q=bq, block_k=bk)

    def ref_loss(q, k, v):
        return (FA._dense(q, k, v, causal, scale) * _f32(dy)).sum()

    def got_loss(q, k, v):
        return (_f32(FA.flash_attention(q, k, v, **kw)) * _f32(dy)).sum()

    o_ref, lse_ref = FA._dense_lse(_f32(q), _f32(k), _f32(v), causal, scale)
    g_ref = jax.grad(ref_loss, (0, 1, 2))(_f32(q), _f32(k), _f32(v))
    o, lse = FA.flash_attention_lse(q, k, v, **kw)
    g = jax.grad(got_loss, (0, 1, 2))(q, k, v)
    assert o.dtype == dtype and lse.dtype == jnp.float32
    assert lse.shape == q.shape[:3]
    if dtype == jnp.float32:      # today's tolerances
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=2e-3, rtol=2e-2)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   atol=2e-3, rtol=2e-2)
        tol = 5e-3
    else:                         # bf16 out and grads round at 2^-9
        _assert_close("out", o, o_ref, 1e-2)
        _assert_close("lse", lse, lse_ref, 1e-2)
        tol = 2e-2
    for name, a, b in zip(("dq", "dk", "dv"), g, g_ref):
        assert a.dtype == dtype
        _assert_close(name, a, b, tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_tiled_walk_lse_cotangent(monkeypatch, dtype):
    """flash_attention_lse with a NON-ZERO lse cotangent (what ring
    attention sends back) through the tiled backward kernels."""
    monkeypatch.setattr(FA, "_TILE", 128)
    t, d = 512, 64
    q, k, v, dy, dlse = _walk_inputs(t, d, dtype, seed=6)
    scale = d ** -0.5

    def loss(att):
        def f(q, k, v):
            o, lse = att(q, k, v)
            return (_f32(o) * _f32(dy)).sum() + (lse * dlse).sum()
        return f

    g_ref = jax.grad(loss(lambda q, k, v: FA._dense_lse(
        q, k, v, True, scale)), (0, 1, 2))(_f32(q), _f32(k), _f32(v))
    g = jax.grad(loss(lambda q, k, v: FA.flash_attention_lse(
        q, k, v, causal=True, force="interpret")), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g, g_ref):
        _assert_close(name, a, b, 5e-3 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("block, target, want", [
    (1024, 256, 256), (2048, 512, 512), (768, 512, 384), (1536, 512, 512),
    (384, 256, 128), (1031, 256, 1031), (128, 256, 128), (64, 256, 64)])
def test_panel_edge_divides_its_block(block, target, want):
    """A panel's edge is a multiple of 128 that divides the major
    block, or the block itself where it has no such divisor."""
    assert FA._tile(block, target) == want


@pytest.mark.parametrize("t, d, dtype, want", [
    (2048, 64, jnp.bfloat16, 2048),    # the benchmark's cell: one block
    (2048, 128, jnp.bfloat16, 2048),
    (1536, 128, jnp.bfloat16, 1536),
    (1024, 64, jnp.float32, 1024),
    (2048, 64, jnp.float32, 1024),     # float32 operands: streamed
    (4096, 64, jnp.bfloat16, 1024),    # too long for one block: streamed
    (2048, 256, jnp.bfloat16, 1024),
    (1536, 128, jnp.float32, 768),
    (2062, 64, jnp.bfloat16, 2)])      # no panel divides it: degenerate
def test_auto_block_is_all_of_t_where_it_fits(t, d, dtype, want):
    assert FA._auto_block(t, d, jnp.dtype(dtype).itemsize) == want


# -- the projections' own layout (PR 29) -------------------------------------
# (H, D) -> heads to a block, lanes of a block: two heads of 64 to a
# 128-lane block; one head of 128, nothing to separate; four heads of
# 32; and heads that fill no whole lane tile (three of 64: 128 / 64
# does not divide 3), all of H*D as one block.
_LAYOUTS = [pytest.param(16, 64, 2, id="H16-D64-g2"),
            pytest.param(4, 128, 1, id="H4-D128-g1"),
            pytest.param(4, 32, 4, id="H4-D32-g4"),
            pytest.param(3, 64, 3, id="H3-D64-whole_width")]


def _bthd_inputs(h, d, dtype, t=256, b=1, seed=7):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape) * 0.5, jnp.float32)
    q, k, v, dy = (mk(b, t, h * d).astype(dtype) for _ in range(4))
    return q, k, v, dy, mk(b, h, t)


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

    def ref_loss(q, k, v):
        o, lse = FA._dense_lse(*(FA.heads_first(x, h) for x in (q, k, v)),
                               causal, scale)
        return (FA.heads_last(o) * _f32(dy)).sum() + (lse * dlse).sum()

    def got_loss(q, k, v):
        o, lse = FA.flash_bthd_lse(q, k, v, h, **kw)
        return (_f32(o) * _f32(dy)).sum() + (lse * dlse).sum()

    o_ref, lse_ref = FA._dense_lse(
        *(FA.heads_first(_f32(x), h) for x in (q, k, v)), causal, scale)
    g_ref = jax.grad(ref_loss, (0, 1, 2))(_f32(q), _f32(k), _f32(v))
    o, lse = FA.flash_bthd_lse(q, k, v, h, **kw)
    grads = jax.grad(got_loss, (0, 1, 2))(q, k, v)
    assert o.shape == q.shape and o.dtype == dtype
    assert lse.shape == (1, h, 256) and lse.dtype == jnp.float32
    # the tolerances of test_tiled_walk_matches_dense
    tol_o, tol_g = (2e-3, 5e-3) if dtype == jnp.float32 else (1e-2, 2e-2)
    _assert_close("out", o, FA.heads_last(o_ref), tol_o)
    _assert_close("lse", lse, lse_ref, tol_o)
    # the output alone, through the other custom_vjp, is the same bits
    assert jnp.array_equal(o, FA.flash_bthd(q, k, v, h, **kw))
    for name, a, b in zip(("dq", "dk", "dv"), grads, g_ref):
        assert a.shape == q.shape and a.dtype == dtype
        _assert_close(name, a, b, tol_g)


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


@pytest.mark.parametrize("h, d, t, dtype, block, want", [
    (16, 64, 2048, jnp.bfloat16, None, "pallas"),   # the benchmark's cell
    (16, 128, 4096, jnp.bfloat16, None, "pallas"),  # g 1, streamed
    (2, 32, 2048, jnp.float32, None, "pallas"),     # whole width, 64 lanes
    (3, 64, 1024, jnp.bfloat16, None, "pallas"),    # whole width, 192 lanes
    (16, 80, 1024, jnp.bfloat16, None, "dense"),    # 1280 lanes: no block
    (1, 192, 768, jnp.float32, None, "pallas"),     # one head: as before
    (16, 60, 1024, jnp.bfloat16, None, "dense"),    # D no multiple of 8
])
def test_block_width_follows_from_d_and_h(monkeypatch, h, d, t, dtype,
                                          block, want):
    monkeypatch.setattr(FA, "_on_tpu", lambda x: True)
    q = jnp.zeros((1, h, t, d), dtype)     # the heads' shape
    assert FA._resolve_path(q, None, block, block, None)[0] == want


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


# -- one backward kernel where a block holds all of T (PR 31) -----------------
def _pallas_names(jaxpr):
    """Names of the pallas_call equations of a jaxpr, sub-jaxprs
    included, in order."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_names(sub)
    return names


@pytest.mark.parametrize("h, d, g", [
    pytest.param(4, 64, 2, id="H4-D64-g2"),
    pytest.param(2, 128, 1, id="H2-D128-g1"),
    pytest.param(3, 64, 3, id="H3-D64-all_of_H")])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("with_dlse", [False, True], ids=["out", "out_lse"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_backward_matches_dense(monkeypatch, dtype, with_dlse, causal,
                                      h, d, g):
    """dq, dk, dv of the ONE backward kernel (all of T 512 in a block,
    four panels of 128 keys, dq accumulated across them in scratch)
    against dense float32 math, with and without an lse cotangent
    folded into the delta the kernel makes and keeps."""
    monkeypatch.setattr(FA, "_TILE", 128)
    monkeypatch.setattr(FA, "_PANEL_SCORES", 128 * 512)
    assert FA.heads_per_block(h, d) == g
    t = 512
    q, k, v, dy, dlse = _bthd_inputs(h, d, dtype, t=t, seed=8)
    scale = d ** -0.5

    def loss(att):
        def f(q, k, v):
            o, lse = att(q, k, v)
            extra = (lse * dlse).sum() if with_dlse else 0.0
            return (_f32(o) * _f32(dy)).sum() + extra
        return f

    def ref(q, k, v):
        o, lse = FA._dense_lse(*(FA.heads_first(x, h) for x in (q, k, v)),
                               causal, scale)
        return FA.heads_last(o), lse

    def got(q, k, v):
        if with_dlse:
            return FA.flash_bthd_lse(q, k, v, h, causal=causal,
                                     force="interpret")
        return FA.flash_bthd(q, k, v, h, causal=causal,
                             force="interpret"), None

    grad = jax.grad(loss(got), (0, 1, 2))
    assert _pallas_names(jax.make_jaxpr(grad)(q, k, v).jaxpr) \
        == ["flash_fwd", "flash_bwd"]
    g_ref = jax.grad(loss(ref), (0, 1, 2))(_f32(q), _f32(k), _f32(v))
    for name, a, b in zip(("dq", "dk", "dv"), grad(q, k, v), g_ref):
        assert a.shape == q.shape and a.dtype == dtype
        _assert_close(name, a, b, 5e-3 if dtype == jnp.float32 else 2e-2)


# sha256 (first 16 hex digits) of dq, dk, dv as float32 bytes, from
# `_streamed_grads` run at commit 88444d7, PR 31's parent, whose
# flash_bwd_dq / flash_bwd_dkv still had their one-block branches. The
# kernels run in interpret mode: XLA's CPU dots, the same on a machine
# whatever the tree.
_PARENT_STREAMED = {
    ("float32", False): ("36def30fa06eecf5", "2fece4931d993ef2", "8d4fe080df03d3c1"),
    ("float32", True): ("42089e908b2c1580", "f74aa46134dec4ec", "8d4fe080df03d3c1"),
    ("bfloat16", False): ("ff8e2ad18a5bdfe6", "0eca2ce7910b981a", "a808b4560d51ddbc"),
    ("bfloat16", True): ("e37a800128f83e9e", "78c549a2470053da", "a808b4560d51ddbc"),
}


def _streamed_grads(dtype, with_dlse):
    """(the gradient function, its arguments) at T 512 in 2 x 2 major
    blocks of 256, two heads of 64 to a block, causal."""
    h, d = 4, 64
    q, k, v, dy, dlse = _bthd_inputs(h, d, dtype, t=512, b=2, seed=9)

    def f(q, k, v):
        kw = dict(causal=True, force="interpret", block_q=256, block_k=256)
        if not with_dlse:
            return (_f32(FA.flash_bthd(q, k, v, h, **kw)) * _f32(dy)).sum()
        o, lse = FA.flash_bthd_lse(q, k, v, h, **kw)
        return (_f32(o) * _f32(dy)).sum() + (lse * dlse).sum()

    return jax.grad(f, (0, 1, 2)), (q, k, v)


@pytest.fixture
def two_kernels(monkeypatch):
    """No shape is within the ONE streamed kernel's byte bound: what is
    traced under this fixture streams through flash_bwd_dq and
    flash_bwd_dkv, as every streamed shape did before ISSUE 39 and as a
    T too long for the bound still does."""
    monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)


@pytest.mark.parametrize("with_dlse", [False, True], ids=["out", "out_lse"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_streamed_backward_is_the_parents_bit_for_bit(two_kernels, dtype,
                                                      with_dlse):
    """Several blocks a sequence, over the byte bound of the one
    streamed kernel: the backward is still flash_bwd_dq then
    flash_bwd_dkv, and every bit of dq, dk, dv is what PR 31's parent
    gave on these inputs."""
    import hashlib
    grad, args = _streamed_grads(dtype, with_dlse)
    assert _pallas_names(jax.make_jaxpr(grad)(*args).jaxpr) \
        == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    got = tuple(hashlib.sha256(np.asarray(_f32(g)).tobytes()
                               ).hexdigest()[:16] for g in grad(*args))
    case = (jnp.dtype(dtype).name, with_dlse)
    assert got == _PARENT_STREAMED[case], (case, got)


@pytest.mark.parametrize("with_dlse", [False, True], ids=["out", "out_lse"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_one_streamed_kernel_is_the_two_kernels_sums(monkeypatch, dtype,
                                                     with_dlse):
    """The same inputs through the ONE streamed kernel (ISSUE 39): the
    forward and flash_bwd, and dq, dk, dv the two kernels' sums in
    another order: float32's rounding apart in float32, a bf16 step of
    the largest value in bf16 (each gradient is rounded once, at its
    store)."""
    grad, args = _streamed_grads(dtype, with_dlse)
    assert _pallas_names(jax.make_jaxpr(grad)(*args).jaxpr) \
        == ["flash_fwd", "flash_bwd"]
    got = grad(*args)
    monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)
    for name, a, b in zip(("dq", "dk", "dv"), got, grad(*args)):
        assert a.dtype == dtype
        _assert_close(name, a, _f32(b), 1e-6 if dtype == jnp.float32
                      else 8e-3)


# the forms the ONE streamed kernel walks, as flash_bthd's arguments
# beside (H, Hkv, D, T, block_q, block_k): T in several major blocks
_STREAMED = [
    pytest.param(2, 2, 128, 512, 128, 128, dict(causal=False), id="full"),
    pytest.param(2, 2, 128, 512, 128, 128, dict(causal=True), id="causal"),
    pytest.param(4, 4, 64, 512, 128, 128, dict(causal=True),
                 id="causal-two_heads_of_64_to_a_block"),
    pytest.param(3, 3, 64, 512, 256, 256, dict(causal=True),
                 id="causal-all_of_H_192_lanes"),
    pytest.param(2, 1, 128, 768, 256, 128, dict(causal=True),
                 id="causal-nq3_nk6"),
    pytest.param(2, 1, 128, 768, 128, 384, dict(causal=True),
                 id="causal-nq6_nk2"),
    pytest.param(2, 1, 128, 512, 512, 128, dict(causal=False),
                 id="full-nq1_nk4"),
    pytest.param(8, 1, 128, 512, 128, 128, dict(causal=True, mask_block=32),
                 id="block_causal-group8"),
    pytest.param(8, 1, 128, 512, 128, 128,
                 dict(causal=True, mask_block=4, strict=True),
                 id="block_causal_strict-group8"),
    pytest.param(8, 1, 128, 512, 128, 128,
                 dict(causal=True, mask_block=4, own_block=True),
                 id="own_block-group8"),
    pytest.param(8, 1, 128, 1024, 256, 256,
                 dict(causal=True, mask_block=128, own_block=True),
                 id="own_block-panels-group8"),
]


@pytest.mark.parametrize("h, hkv, d, t, bq, bk, form", _STREAMED)
@pytest.mark.parametrize("backward", ["fused_streamed", "two_kernels"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_streamed_backward_matches_dense(monkeypatch, dtype, backward, h,
                                         hkv, d, t, bq, bk, form):
    """dq, dk, dv of a streamed T against dense float32 math with the
    mask written out, through the ONE kernel (dq for all rows in VMEM
    scratch across the key blocks, delta made at a q block's first
    visit) and, the byte bound set to nothing, through the two kernels
    it replaced: every mask form, grouped heads 8:1, two heads to a
    block and all of H, unequal counts of q and key blocks, and an lse
    cotangent where the form gives an lse."""
    monkeypatch.setattr(FA, "_TILE", 128)
    if backward == "two_kernels":
        monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)
    own = form.get("own_block", False)
    q, k, v, dy, dlse = _gqa_inputs(h, hkv, d, t, dtype, seed=17)
    kw = dict(force="interpret", block_q=bq, block_k=bk, n_kv_head=hkv,
              **form)
    mask_block, strict = form.get("mask_block", 1), form.get("strict", False)

    def dense(q, k, v):
        if not form["causal"]:
            o, lse = FA._dense_lse(
                FA.heads_first(q, h), FA.heads_first(k, hkv),
                FA.heads_first(v, hkv), False, d ** -0.5)
            return FA.heads_last(o), lse, jnp.ones((t,), bool)
        return _dense_block_causal(q, k, v, h, hkv, mask_block, strict, own)

    seen = dense(q, k, v)[2]

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            w = seen.astype(jnp.float32)
            out = (_f32(o) * _f32(dy) * w[None, :, None]).sum()
            return out if own else out + (
                jnp.where(seen, lse, 0.0) * dlse).sum()
        return f

    def run(q, k, v):
        if own:                 # the form gives no lse
            return FA.flash_bthd(q, k, v, h, **kw), None
        return FA.flash_bthd_lse(q, k, v, h, **kw)

    grad = jax.grad(loss(run), (0, 1, 2))
    assert _pallas_names(jax.make_jaxpr(grad)(q, k, v).jaxpr) == [
        "flash_fwd"] + {"fused_streamed": ["flash_bwd"], "two_kernels": [
            "flash_bwd_dq", "flash_bwd_dkv"]}[backward]
    want = jax.grad(loss(lambda *a: dense(*a)[:2]), (0, 1, 2))(
        _f32(q), _f32(k), _f32(v))
    tol = 5e-3 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("dq", "dk", "dv"), grad(q, k, v), want):
        assert a.shape == b.shape and a.dtype == dtype
        assert bool(jnp.isfinite(_f32(a)).all()), name
        _assert_close(name, a, b, tol)


@pytest.mark.parametrize("t, w, block, want", [
    (2048, 128, 2048, "fused"),          # the benchmark's cell
    (512, 128, 1024, "fused"),           # a block no longer than T
    (4096, 128, 1024, "fused_streamed"),     # OLMoE's: streamed
    (2048, 128, 1024, "fused_streamed"),     # float32 at T 2048
    (512, 192, 512, "fused"),            # all of H*D, 192 lanes
    (1024, 192, 1024, "fused_streamed"),     # the same clamped to 512 rows
    (16384, 128, 1024, "fused_streamed"),    # Trinity's: 16 MiB resident
    (32768, 128, 1024, "fused_streamed"),    # the last T within the bound
    (65536, 128, 1024, "two_kernels"),   # ring attention's longest shards
    (16384, 512, 512, "two_kernels"),    # all of H*D, 512 lanes: 64 MiB
])
def test_backward_follows_from_the_blocks(t, w, block, want):
    """One kernel exactly where the backward's blocks, after the VMEM
    clamp of wide blocks, hold all of T; the ONE streamed kernel where
    dq for all rows of a block of heads (float32, and its output block
    twice) is within _RESIDENT_DQ_BYTES; the two kernels beyond: no
    flag decides it."""
    assert FA._backward_of(t, w, block, block) == want
    bq, bk = FA._backward_blocks(t, w, block, block)
    assert t % bq == 0 and t % bk == 0


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


# -- grouped key/value heads and the block-granular mask (ISSUE 32) ----------

def _gqa_inputs(h, hkv, d, t, dtype, seed=11):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape) * 0.5,
                                    jnp.float32).astype(dtype)
    return (mk(1, t, h * d), mk(1, t, hkv * d), mk(1, t, hkv * d),
            mk(1, t, h * d), jnp.asarray(rng.randn(1, h, t) * 0.5,
                                         jnp.float32))


def _dense_block_causal(q, k, v, h, hkv, mask_block, strict, own=False):
    """(out [B, T, H*D], lse [B, H, T], seen [T]) by dense float32 math
    with the mask written out: query i sees key j iff
    j // m + strict <= i // m. `own` (ISSUE 37): the T rows are two
    halves at the same positions, [noised; clean]; a clean key is seen
    from its block on by the clean queries and from the block after by
    the noised ones, a noised key by the noised queries of its block."""
    b, t, hd = q.shape
    d = hd // h
    qh = FA.heads_first(_f32(q), h)
    kh, vh = (jnp.repeat(FA.heads_first(_f32(x), hkv), h // hkv, 1)
              for x in (k, v))
    at = jnp.arange(t) // mask_block
    seen = at[None, :] + int(strict) <= at[:, None]
    if own:
        at = np.arange(t // 2) // mask_block
        ahead, same = at[None, :] < at[:, None], at[None, :] == at[:, None]
        seen = jnp.asarray(np.block([[same, ahead],
                                     [np.zeros_like(same), ahead | same]]))
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * d ** -0.5
    s = jnp.where(seen, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, -1)
    p = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(lse), lse,
                                              0.0)[..., None]), 0.0)
    return (FA.heads_last(jnp.einsum("bhqk,bhkd->bhqd", p, vh)), lse,
            seen.any(1))


@pytest.mark.parametrize("mask_block, strict", [
    (1, False), (4, False), (4, True), (32, False), (32, True)],
    ids=["causal", "b4", "b4_strict", "b32", "b32_strict"])
@pytest.mark.parametrize("block", [None, 128], ids=["one_block", "streamed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_grouped_kv_block_causal_matches_dense(dtype, block, mask_block,
                                               strict):
    """4 query heads of 128 reading 2 key/value heads under each mask,
    one block and streamed, in interpret mode against dense float32
    math with the mask written out: out, lse, dq, and dk, dv summed over
    each group, with a non-zero lse cotangent. Under `strict` the first
    block's rows see nothing: their out is finite, their lse -1e30, and
    weighed out (as a merge by lse weighs them) they leave every
    gradient finite and right."""
    h, hkv, d, t = 4, 2, 128, 256
    q, k, v, dy, dlse = _gqa_inputs(h, hkv, d, t, dtype)
    kw = dict(causal=True, force="interpret", block_q=block, block_k=block,
              n_kv_head=hkv, mask_block=mask_block, strict=strict)
    o_ref, lse_ref, seen = _dense_block_causal(q, k, v, h, hkv, mask_block,
                                               strict)
    assert int((~seen).sum()) == (mask_block if strict else 0)
    o, lse = FA.flash_bthd_lse(q, k, v, h, **kw)
    assert o.shape == q.shape and lse.shape == (1, h, t)
    assert bool(jnp.isfinite(_f32(o)).all())
    tol = 5e-3 if dtype == jnp.float32 else 2e-2
    _assert_close("out", jnp.where(seen[None, :, None], o, 0), o_ref, tol)
    _assert_close("lse", jnp.where(seen, lse, 0),
                  jnp.where(seen, lse_ref, 0), tol)
    assert bool((jnp.where(seen, 0, lse) <= 0).all())
    assert bool((lse[..., ~np.asarray(seen)] < -1e29).all())

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            w = seen.astype(jnp.float32)
            return (_f32(o) * _f32(dy) * w[None, :, None]).sum() \
                + (jnp.where(seen, lse, 0.0) * dlse).sum()
        return f

    got = jax.grad(loss(lambda q, k, v: FA.flash_bthd_lse(q, k, v, h, **kw)),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _dense_block_causal(
        q, k, v, h, hkv, mask_block, strict)[:2]), (0, 1, 2))(
            _f32(q), _f32(k), _f32(v))
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == dtype
        assert bool(jnp.isfinite(_f32(a)).all()), name
        _assert_close(name, a, b, tol)


def test_block_mask_of_one_row_is_causal_bit_for_bit():
    """`mask_block` 1 without `strict` IS causal: the same kernels on
    the same operands, every bit."""
    h, hkv, d, t = 4, 2, 128, 256
    q, k, v, dy, _ = _gqa_inputs(h, hkv, d, t, jnp.bfloat16)
    def grads(**kw):
        f = lambda q, k, v: (_f32(FA.flash_bthd(
            q, k, v, h, causal=True, force="interpret", n_kv_head=hkv,
            block_q=128, block_k=128, **kw)) * _f32(dy)).sum()
        return jax.value_and_grad(f, (0, 1, 2))(q, k, v)
    (a, ga), (b, gb) = grads(), grads(mask_block=1, strict=False)
    assert float(a) == float(b)
    for x, y in zip(ga, gb):
        assert bool((x == y).all())


def test_what_the_kernels_cannot_take_goes_dense():
    """A group of query heads shares a block of k only where a block is
    one head, and a mask's block must divide the tiles: anything else is
    dense math, also when a caller forces the kernel; the counter's
    `mask` and `kv_groups` labels say what was asked."""
    count = FA._LOWERINGS
    q, k, v, _, _ = _gqa_inputs(4, 2, 64, 256, jnp.float32)
    labels = dict(path="dense", entry="bthd", heads_per_block="2",
                  backward="none", mask="block_causal_strict", kv_groups="2",
                  key_width="64", value_width="64", second_part="none", window="0")
    was = count.value(**labels)
    o = FA.flash_bthd(q, k, v, 4, causal=True, force="interpret",
                      n_kv_head=2, mask_block=4, strict=True)
    assert count.value(**labels) == was + 1
    o_ref, _, seen = _dense_block_causal(q, k, v, 4, 2, 4, True)
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


# -- the own-block form (ISSUE 37): block diffusion inside the kernels -------

@pytest.mark.parametrize("seq, block", [(256, None), (1024, 512)],
                         ids=["one_block", "streamed"])
@pytest.mark.parametrize("mask_block", [4, 128], ids=["b4", "b128"])
@pytest.mark.parametrize("h, hkv", [(2, 2), (4, 2), (8, 1)],
                         ids=["group1", "group2", "group8"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_own_block_form_matches_dense(dtype, h, hkv, mask_block, seq, block):
    """[noised; clean] rows through ONE call of each kernel, in
    interpret mode against dense float32 math with the 2L x 2L mask
    written out: out, dq, and dk, dv of both halves, summed over each
    group. A half in one block (one masked panel; forward grid
    (., 2, 1)) and in two streamed blocks of 512 (two panels of 256
    each on the diagonal); the backward is the ONE streamed kernel
    either way (ISSUE 39), since the rows are never one block."""
    d = 128
    q, k, v, dy, _ = _gqa_inputs(h, hkv, d, 2 * seq, dtype, seed=3)
    kw = dict(causal=True, force="interpret", block_q=block, block_k=block,
              n_kv_head=hkv, mask_block=mask_block, own_block=True)
    dense = lambda q, k, v: _dense_block_causal(
        q, k, v, h, hkv, mask_block, False, own=True)[0]
    run = lambda q, k, v: FA.flash_bthd(q, k, v, h, **kw)
    tol = 5e-3 if dtype == jnp.float32 else 2e-2
    o = run(q, k, v)
    assert o.shape == q.shape and o.dtype == dtype
    _assert_close("out", o, dense(q, k, v), tol)
    loss = lambda fn: lambda *a: (_f32(fn(*a)) * _f32(dy)).sum()
    grad = jax.grad(loss(run), (0, 1, 2))
    assert _pallas_names(jax.make_jaxpr(grad)(q, k, v).jaxpr) \
        == ["flash_fwd", "flash_bwd"]
    want = jax.grad(loss(dense), (0, 1, 2))(_f32(q), _f32(k), _f32(v))
    for name, a, b in zip(("dq", "dk", "dv"), grad(q, k, v), want):
        assert a.shape == b.shape and a.dtype == dtype
        for half, rows in (("noised", slice(0, seq)),
                           ("clean", slice(seq, None))):
            _assert_close(name + " " + half, a[:, rows], b[:, rows], tol)


def test_own_blocks_first_noised_rows_see_themselves_alone():
    """The first block's noised rows see no clean key (a strict call
    gave them lse -1e30 for the merge to weigh out): inside the kernels
    their own block is all their softmax runs over, so their output is
    the attention of q's first rows on the noised keys of those rows,
    whatever the clean half holds."""
    h, hkv, d, seq, m = 4, 2, 128, 256, 4
    q, k, v, _, _ = _gqa_inputs(h, hkv, d, 2 * seq, jnp.float32, seed=5)
    run = lambda k, v: FA.flash_bthd(
        q, k, v, h, causal=True, force="interpret", n_kv_head=hkv,
        mask_block=m, own_block=True, block_q=128, block_k=128)
    o = run(k, v)
    alone = _dense_block_causal(q[:, :m], k[:, :m], v[:, :m], h, hkv, m,
                                False)[0]
    _assert_close("first block", o[:, :m], alone, 1e-5)
    other = run(k.at[:, seq:].multiply(-3.0), v.at[:, seq:].add(1.0))
    assert bool((other[:, :m] == o[:, :m]).all())
    assert not bool((other[:, m:2 * m] == o[:, m:2 * m]).all())


def test_own_block_form_counts_itself_and_goes_dense_where_it_must():
    """The counter's `mask` label reads `block_causal_own`, the backward
    `fused_streamed` also where a half is one block; what the kernels
    cannot take (unequal blocks, two heads of 64 under grouped keys) is
    the dense mask; `strict`, a full mask or an odd count of rows with
    the form is a ValueError."""
    count = FA._LOWERINGS
    q, k, v, _, _ = _gqa_inputs(4, 2, 128, 512, jnp.float32)
    labels = dict(path="interpret", entry="bthd", heads_per_block="1",
                  backward="fused_streamed", mask="block_causal_own",
                  kv_groups="2", key_width="128", value_width="128",
                  second_part="none", window="0")
    was = count.value(**labels)
    kw = dict(causal=True, force="interpret", n_kv_head=2, mask_block=4,
              own_block=True)
    o = FA.flash_bthd(q, k, v, 4, **kw)
    assert count.value(**labels) == was + 1
    labels.update(path="dense", backward="none")
    was = count.value(**labels)
    o_dense = FA.flash_bthd(q, k, v, 4, block_q=128, block_k=256, **kw)
    assert count.value(**labels) == was + 1
    want = _dense_block_causal(q, k, v, 4, 2, 4, False, own=True)[0]
    _assert_close("kernels", o, want, 1e-5)
    _assert_close("dense", o_dense, want, 1e-5)
    q, k, v, _, _ = _gqa_inputs(4, 2, 64, 512, jnp.float32)
    labels.update(heads_per_block="2", key_width="64", value_width="64")
    was = count.value(**labels)
    FA.flash_bthd(q, k, v, 4, **kw)
    assert count.value(**labels) == was + 1
    for bad in (dict(strict=True), dict(causal=False)):
        with pytest.raises(ValueError):
            FA.flash_bthd(q, k, v, 4, **dict(kw, **bad))
    with pytest.raises(ValueError):
        FA.flash_bthd(q[:, :255], k[:, :255], v[:, :255], 4, **kw)
    assert "block_causal_own" in count.help


# sha256 (first 16 hex digits) of dq, dk, dv as float32 bytes from the
# ONE-block causal backward (flash_bwd, two heads of 64 to a block: the
# path the benchmark's OPT cell takes) at commit a539599, PR 32's parent,
# before the kernels learnt a mask and a group size.
_PARENT_FUSED = {
    ("float32", False): ("1848e6b5aed696ee", "f7afa5d50d2ee3ab", "c03bbf9fcf6e3b7b"),
    ("float32", True): ("5e96e14ece489662", "1a733a770f92ac14", "c03bbf9fcf6e3b7b"),
    ("bfloat16", False): ("7d7c2e4e9466d224", "b311016cf14f5045", "af90e6f377929259"),
    ("bfloat16", True): ("9bb9de23b235f79a", "21cba15f2d22ecae", "af90e6f377929259"),
}


@pytest.mark.parametrize("with_dlse", [False, True], ids=["out", "out_lse"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_causal_backward_is_the_parents_bit_for_bit(dtype, with_dlse):
    """The causal path of the OPT cell (g 2, one block, flash_bwd)
    lowers to the kernels it had: every bit of dq, dk, dv is what PR
    32's parent gave at T 512."""
    import hashlib
    h, d = 4, 64
    q, k, v, dy, dlse = _bthd_inputs(h, d, dtype, t=512, b=2, seed=9)

    def f(q, k, v):
        kw = dict(causal=True, force="interpret")
        if not with_dlse:
            return (_f32(FA.flash_bthd(q, k, v, h, **kw)) * _f32(dy)).sum()
        o, lse = FA.flash_bthd_lse(q, k, v, h, **kw)
        return (_f32(o) * _f32(dy)).sum() + (lse * dlse).sum()

    grad = jax.grad(f, (0, 1, 2))
    assert _pallas_names(jax.make_jaxpr(grad)(q, k, v).jaxpr) \
        == ["flash_fwd", "flash_bwd"]
    got = tuple(hashlib.sha256(np.asarray(_f32(g)).tobytes()
                               ).hexdigest()[:16] for g in grad(q, k, v))
    case = (jnp.dtype(dtype).name, with_dlse)
    assert got == _PARENT_FUSED[case], (case, got)
