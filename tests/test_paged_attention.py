"""paddle_tpu.ops.paged_attention + the ISSUE-20 serving wiring.

tests/test_serving.py and tests/test_kvpool.py already gate the broad
paged contract against sequential decode with the engine DEFAULT —
which since ISSUE 20 is the block-chain kernel, so slot recycling,
multi-chunk prefill, mid-flight admission, bf16, megastep K>1, COW,
preemption-resume and speculative decode all ride it there. This
module holds the pins the kernel tier itself needs:

  * kernel math vs a dense-softmax reference: the lax chain-walk path
    (grouped and ungrouped), the 5-D full-pool + static-layer calling
    shape, the γ+1 multi-query shape, and the dynamic ``nblk`` bound;
  * interpret-mode Pallas parity (tests/test_flash_entries.py
    style): the TPU kernel's math checked on CPU via interpret=True
    against the lax reference;
  * the EXPLICIT block-vs-gather A/B the identity lattice rests on:
    engine outputs with ``serving_block_kernel`` on vs off, token-
    identical through recycling + chunked prefill, the prefix-cache/
    COW path, preemption-resume, megastep K>1, and the γ+1
    speculative scoring entry (model-level, one dispatch);
  * int8 KV quantization: quantize/dequantize round-trip bounds,
    kernel output pinned at rtol 2e-2 (derivation at the pin), the
    engine arm deterministic and OFF by default, and the quant-aware
    ``bytes_per_block`` / ``plan_hbm_bytes`` accounting.

Budget: ONE module-scoped 1-layer LM (the test_kvpool shape) + three
small engines; kernel-math tests are pure-array. Soaks live behind
``-m slow``.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.models import transformer
from paddle_tpu.models.transformer_infer import TransformerLMInfer
from paddle_tpu.ops import paged_attention as P
from paddle_tpu.serving import kvpool
from paddle_tpu.transform import autoparallel as ap

N_LAYER, N_HEAD, D_MODEL, MAX_LEN, VOCAB = 1, 2, 32, 32, 40
BS = 4


# -- kernel math vs dense reference ----------------------------------------

def _rand_case(rng, s=3, l=2, h=2, bs=8, dk=16, w=4, c=1):
    """One random paged-attention problem + its dense-softmax answer."""
    nb = l * 0 + s * w + 2            # a couple of spare blocks
    pk = jnp.asarray(rng.normal(size=(nb, l, h, bs, dk)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(nb, l, h, bs, dk)), jnp.float32)
    btab = jnp.asarray(rng.permutation(nb)[:s * w].reshape(s, w),
                       jnp.int32)
    qpos = jnp.asarray(rng.integers(0, w * bs, size=(s, c)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(s, h, c, dk)), jnp.float32)
    return pk, pv, btab, qpos, q


def _dense_ref(pk, pv, btab, qpos, q, layer):
    s, h, c, dk = q.shape
    w, bs = btab.shape[1], pk.shape[-2]
    k = pk[btab, layer].transpose(0, 2, 1, 3, 4).reshape(s, h, -1, dk)
    v = pv[btab, layer].transpose(0, 2, 1, 3, 4).reshape(s, h, -1, dk)
    sc = jnp.einsum("shcd,shkd->shck", q, k)
    kpos = jnp.arange(w * bs)
    sc = jnp.where(kpos[None, None, None, :] <= qpos[:, None, :, None],
                   sc, -1e30)
    return jnp.einsum("shck,shkd->shcd",
                      jax.nn.softmax(sc, axis=-1), v)


def test_kernel_matches_dense_reference():
    """The lax chain-walk (grouped and not, 4-D slice and 5-D+layer
    calling shapes, single-query and γ+1) reproduces the dense
    softmax to accumulation-order rounding."""
    rng = np.random.default_rng(0)
    for c in (1, 5):                    # decode step and γ+1 scoring
        pk, pv, btab, qpos, q = _rand_case(rng, c=c)
        ref = _dense_ref(pk, pv, btab, qpos, q, 1)
        for grp in (1, 3):
            o = P.paged_attention(q, pk, pv, btab, qpos, layer=1,
                                  block_group=grp, force="lax")
            np.testing.assert_allclose(o, ref, rtol=2e-5, atol=2e-5)
        o4 = P.paged_attention(q, pk[:, 1], pv[:, 1], btab, qpos,
                               force="lax")
        np.testing.assert_allclose(o4, ref, rtol=2e-5, atol=2e-5)


def test_interpret_mode_pallas_parity():
    """The Pallas kernel's math, interpret-executed on CPU, matches
    the lax reference path — fp32 and quantized, both pool shapes."""
    rng = np.random.default_rng(1)
    pk, pv, btab, qpos, q = _rand_case(rng, c=3)
    for args in ((pk, pv, {}), (pk[:, 0], pv[:, 0], {})):
        a, b, kw = args
        layer = 0 if a.ndim == 5 else None
        o_lax = P.paged_attention(q, a, b, btab, qpos, layer=layer,
                                  force="lax")
        o_int = P.paged_attention(q, a, b, btab, qpos, layer=layer,
                                  force="interpret")
        np.testing.assert_allclose(o_int, o_lax, rtol=1e-5, atol=1e-5)
    ck, sk = P.quantize_kv(pk, jnp.int8)
    cv, sv = P.quantize_kv(pv, jnp.int8)
    o_lax = P.paged_attention(q, ck, cv, btab, qpos, k_scale=sk,
                              v_scale=sv, layer=0, force="lax")
    o_int = P.paged_attention(q, ck, cv, btab, qpos, k_scale=sk,
                              v_scale=sv, layer=0, force="interpret")
    np.testing.assert_allclose(o_int, o_lax, rtol=1e-5, atol=1e-5)


def test_nblk_bounds_the_walk():
    """Rows the dynamic chain bound covers are exact; the bound is a
    TRACED scalar (works under jit — the megastep scan carries it)."""
    rng = np.random.default_rng(2)
    pk, pv, btab, qpos, q = _rand_case(rng)
    bs, w = pk.shape[-2], btab.shape[1]
    qpos = qpos.at[0].set(bs - 1)       # slot 0: one block held
    qpos = qpos.at[1:].set(2 * bs)      # others: three blocks
    ref = _dense_ref(pk, pv, btab, qpos, q, 0)
    run = jax.jit(lambda n: P.paged_attention(
        q, pk, pv, btab, qpos, nblk=n, layer=0, force="lax"))
    # nblk=3 covers every live chain -> all rows exact
    np.testing.assert_allclose(run(jnp.int32(3)), ref, rtol=2e-5,
                               atol=2e-5)
    # nblk=1 covers only slot 0; its row must still be exact
    np.testing.assert_allclose(run(jnp.int32(1))[0], ref[0],
                               rtol=2e-5, atol=2e-5)


def test_pool_layer_shape_validation():
    rng = np.random.default_rng(3)
    pk, pv, btab, qpos, q = _rand_case(rng)
    with pytest.raises(ValueError):     # 5-D pool needs layer
        P.paged_attention(q, pk, pv, btab, qpos, force="lax")
    with pytest.raises(ValueError):     # 4-D slice forbids layer
        P.paged_attention(q, pk[:, 0], pv[:, 0], btab, qpos, layer=0,
                          force="lax")


# -- int8 KV quantization --------------------------------------------------

def test_quantize_dequantize_roundtrip():
    """Symmetric per-vector int8: every element lands within scale/2 =
    amax/254 of its source; all-zero vectors round-trip exactly
    (scale pins to 1 so block 0's zeros stay zeros)."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(6, 5, 16)) * 3.0, jnp.float32)
    codes, scale = P.quantize_kv(x, jnp.int8)
    assert codes.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    y = P.dequantize_kv(codes, scale)
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    assert np.all(np.abs(np.asarray(y) - np.asarray(x))
                  <= amax / 254.0 + 1e-7)
    z_codes, z_scale = P.quantize_kv(jnp.zeros((2, 8)), jnp.int8)
    assert np.all(np.asarray(z_scale) == 1.0)
    assert np.all(np.asarray(P.dequantize_kv(z_codes, z_scale)) == 0.0)


def test_kv_quant_spec_validation():
    assert P.kv_quant_spec(None) is None
    assert P.kv_quant_spec("") is None
    dt, qmax = P.kv_quant_spec("int8")
    assert dt == jnp.int8 and qmax == 127.0
    with pytest.raises(ValueError):
        P.kv_quant_spec("int4")
    assert P.kv_quant_spec("fp8") == (jnp.float8_e4m3fn, 448.0)


def test_quantized_kernel_rtol_pin():
    """The documented error budget: int8 rounds each K/V element to
    within scale/2 = amax/254 (<= ~0.4% relative per element); scores
    perturb by O(dk * 0.4% / sqrt(dk)) and the softmax output is a
    convex combination of perturbed V rows, measured ~1% relative on
    random problems. Pinned at rtol 2e-2 — the same margin class as
    the bf16 serving pass (2^-8 mantissa ~ 0.4%/element there)."""
    rng = np.random.default_rng(5)
    pk, pv, btab, qpos, q = _rand_case(rng, c=2)
    ref = _dense_ref(pk, pv, btab, qpos, q, 1)
    ck, sk = P.quantize_kv(pk, jnp.int8)
    cv, sv = P.quantize_kv(pv, jnp.int8)
    o = P.paged_attention(q, ck, cv, btab, qpos, k_scale=sk,
                          v_scale=sv, layer=1, force="lax")
    err = float(jnp.max(jnp.abs(o - ref)) / jnp.max(jnp.abs(ref)))
    assert err < 2e-2, "int8 KV error %.4f breaches the budget" % err


def test_bytes_per_block_quant_accounting():
    # quantized: 1 code byte per element + one f32 scale per
    # (position, head) vector, K and V
    assert kvpool.bytes_per_block(3, 4, 16, 64, 4, kv_quant="int8") \
        == 2 * 3 * 4 * 16 * (64 + 4)
    # dense pricing unchanged; "", "none" and None all mean dense
    dense = kvpool.bytes_per_block(3, 4, 16, 64, 4)
    assert dense == 2 * 3 * 4 * 16 * 64 * 4
    assert kvpool.bytes_per_block(3, 4, 16, 64, 4, kv_quant="") \
        == dense
    # an fp32 dk-64 pool drops to (64 + 4) / 256 = ~27% of dense
    assert kvpool.bytes_per_block(3, 4, 16, 64, 4, kv_quant="int8") \
        < dense * 0.3


def test_plan_hbm_bytes_prices_quantized_pool():
    spec = ap.ModelSpec("m", 1e9, 1e9, 4e6, batch=8, seq=256,
                        d_model=256, n_layer=4, n_head=8)
    axes = {"dp": 1, "tp": 1, "pp": 1, "sp": 1, "ep": 1}
    dense, dbd = ap.plan_hbm_bytes(spec, axes)
    quant, qbd = ap.plan_hbm_bytes(spec, axes, kv_quant="int8")
    assert qbd["hbm_kv_bytes"] < dbd["hbm_kv_bytes"] * 0.35
    assert dbd["hbm_param_bytes"] == qbd["hbm_param_bytes"]
    # spec.kv_quant is the fallback when the call leaves it None
    spec.kv_quant = "int8"
    auto, abd = ap.plan_hbm_bytes(spec, axes)
    assert abd["hbm_kv_bytes"] == qbd["hbm_kv_bytes"]


# -- the explicit block-vs-gather engine A/B -------------------------------

@pytest.fixture(scope="module")
def lm():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        transformer.transformer_lm(
            vocab_size=VOCAB, max_len=MAX_LEN, n_layer=N_LAYER,
            n_head=N_HEAD, d_model=D_MODEL, d_inner=64)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return TransformerLMInfer(main, scope, N_LAYER, N_HEAD,
                                  D_MODEL, MAX_LEN, end_id=VOCAB)


@pytest.fixture(scope="module")
def eng_block(lm):
    e = serving.Engine(lm, slots=2, prefill_chunk=4, block_size=BS)
    assert e._block_kernel        # the flag default selects the kernel
    yield e
    e.close()


@pytest.fixture(scope="module")
def eng_gather(lm):
    """The serving_block_kernel=0 escape hatch: the PR-10 dense-gather
    math, the identity baseline of every A/B below."""
    e = serving.Engine(lm, slots=2, prefill_chunk=4, block_size=BS,
                      block_kernel=False)
    assert not e._block_kernel
    yield e
    e.close()


def _ab(eng_a, eng_b, reqs):
    oa = eng_a.generate_many([p for p, _ in reqs],
                             [m for _, m in reqs])
    ob = eng_b.generate_many([p for p, _ in reqs],
                             [m for _, m in reqs])
    for i, ((at, ascore), (bt, bscore)) in enumerate(zip(oa, ob)):
        assert at == bt, "request %d diverged: %r vs %r" % (i, at, bt)
        np.testing.assert_allclose(ascore, bscore, rtol=1e-5,
                                   atol=1e-5)
    return oa


def test_block_vs_gather_recycling_and_chunked_prefill(lm, eng_block,
                                                       eng_gather):
    """6 mixed requests through 2 slots: recycling + prompts longer
    than the prefill chunk, token-identical across the two paths."""
    rng = np.random.RandomState(20)
    reqs = []
    for _ in range(6):
        plen = int(rng.randint(1, 11))
        reqs.append(([1] + rng.randint(3, VOCAB, plen - 1).tolist(),
                     int(rng.randint(4, 12))))
    _ab(eng_block, eng_gather, reqs)


def test_block_vs_gather_prefix_cache_and_cow(lm, eng_block,
                                              eng_gather):
    """Shared system prompt across requests: the cached chain is read
    through both paths, and the fully block-aligned prompt exercises
    the COW first-decode write — identical either way."""
    rng = np.random.RandomState(21)
    sysp = [1] + rng.randint(3, VOCAB, 9).tolist()
    reqs = [(list(sysp) + rng.randint(3, VOCAB, 2).tolist(), 6)
            for _ in range(4)]
    reqs.append((list(sysp[:2 * BS]), 6))   # block-aligned -> COW
    _ab(eng_block, eng_gather, reqs)


def test_block_vs_gather_preemption_resume(lm):
    """A pool too small for two long requests preempts and resumes
    under BOTH paths; outputs stay identical and both engines really
    preempted (the pressure reached the preemption path)."""
    reqs = [([1, 4, 7], 18), ([1, 5, 9], 18)]
    engs = [serving.Engine(lm, slots=2, prefill_chunk=4, block_size=BS,
                           num_blocks=9, prefix_cache=False,
                           block_kernel=bk, name="pre-%s" % bk)
            for bk in (True, False)]
    try:
        outs = [e.generate_many([p for p, _ in reqs],
                                [m for _, m in reqs]) for e in engs]
        for (at, _), (bt, _) in zip(*outs):
            assert at == bt
        assert all(e.stats["preemptions"] >= 1 for e in engs)
    finally:
        for e in engs:
            e.close()


def test_block_vs_gather_megastep(lm, eng_gather):
    """K>1 fused decode: the block kernel's dynamic chain walk runs
    INSIDE the megastep scan (a while_loop under the scan body) —
    tokens stay pinned to the gather path."""
    e = serving.Engine(lm, slots=2, prefill_chunk=4, block_size=BS,
                      megastep=3, name="mega-block")
    try:
        rng = np.random.RandomState(22)
        reqs = [([1] + rng.randint(3, VOCAB, 3).tolist(),
                 int(rng.randint(6, 12))) for _ in range(4)]
        _ab(e, eng_gather, reqs)
    finally:
        e.close()


def test_spec_logits_block_vs_gather(lm):
    """The γ+1 speculative scoring entry (one dispatch, C = 4):
    per-position argmax and logits agree across the two paths."""
    s, c = 2, 4
    nbs = MAX_LEN // BS
    rng = np.random.RandomState(23)
    btab = jnp.arange(s * nbs, dtype=jnp.int32).reshape(s, nbs)
    toks = jnp.asarray(rng.randint(3, VOCAB, (s, c)), jnp.int32)
    pos = jnp.asarray([5, 9], jnp.int32)
    outs = []
    for bk in (True, False):
        state = lm._init_paged_state(s * nbs, BS)
        logits, _ = lm._spec_logits_paged(
            toks, state, pos, btab, jnp.full((s,), c, jnp.int32),
            block_kernel=bk)
        outs.append(np.asarray(logits))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-4, atol=1e-4)
    assert np.array_equal(outs[0].argmax(-1), outs[1].argmax(-1))


def test_quantized_engine_off_by_default_deterministic(lm, eng_block):
    """int8 KV is opt-in (flag default ''), the quantized engine's
    bytes accounting shrinks, and its greedy output is deterministic
    run-over-run (quantize-on-write is a pure function)."""
    assert eng_block._kv_quant is None
    reqs = [([1, 6, 11], 8), ([1, 7, 3], 8)]
    e = serving.Engine(lm, slots=2, prefill_chunk=4, block_size=BS,
                      kv_quant="int8", name="quant")
    try:
        assert e._kv_quant == "int8"
        assert e._block_bytes < eng_block._block_bytes
        assert e._block_bytes == kvpool.bytes_per_block(
            N_LAYER, N_HEAD, BS, D_MODEL // N_HEAD, kv_quant="int8")
        a = e.generate_many([p for p, _ in reqs], [m for _, m in reqs])
        b = e.generate_many([p for p, _ in reqs], [m for _, m in reqs])
        assert [t for t, _ in a] == [t for t, _ in b]
    finally:
        e.close()
    # dense engines refuse the flag combination outright
    with pytest.raises(ValueError):
        serving.Engine(lm, slots=2, paged=False, kv_quant="int8")


def test_low_precision_pool_defaults_to_gather():
    """The bf16 serving cast's identity contract is BITWISE vs the
    bf16 sequential baseline, and only the gather path reruns that
    exact row math — the kernel accumulates in fp32, a different
    reduction order. So a low-precision un-quantized pool resolves
    the flag default to gather; explicit opt-in and quantized pools
    (rtol-pinned, never bitwise) still take the kernel."""
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        transformer.transformer_lm(
            vocab_size=VOCAB, max_len=MAX_LEN, n_layer=N_LAYER,
            n_head=N_HEAD, d_model=D_MODEL, d_inner=64)
        fluid.Executor(fluid.CPUPlace()).run(startup)
        bf = TransformerLMInfer(main, scope, N_LAYER, N_HEAD, D_MODEL,
                                MAX_LEN, dtype=jnp.bfloat16,
                                end_id=VOCAB)
    with serving.Engine(bf, slots=2, block_size=BS, name="bfd") as e:
        assert not e._block_kernel
    with serving.Engine(bf, slots=2, block_size=BS, name="bfk",
                        block_kernel=True) as e:
        assert e._block_kernel
    with serving.Engine(bf, slots=2, block_size=BS, name="bfq",
                        kv_quant="int8") as e:
        assert e._block_kernel


def test_kv_bytes_telemetry(lm, eng_block):
    """The effective-bytes companions: gauges land block-count x the
    engine's quant-aware bytes_per_block after a paged run."""
    from paddle_tpu.monitor import runtime as monrt
    eng_block.generate_many([[1, 8, 2]], [4])
    total = monrt.KV_BYTES_TOTAL.value()
    assert total == eng_block._pool.num_blocks * eng_block._block_bytes


# -- soak ------------------------------------------------------------------

@pytest.mark.slow
def test_kernel_soak_random_shapes():
    """Wider sweep: random (S, H, bs, dk, W, C) problems, lax and
    interpret paths, fp32 and int8, against the dense reference."""
    rng = np.random.default_rng(6)
    for _ in range(12):
        s = int(rng.integers(1, 5))
        h = int(rng.integers(1, 4))
        bs = int(rng.choice([4, 8, 16]))
        dk = int(rng.choice([8, 16, 32]))
        w = int(rng.integers(2, 6))
        c = int(rng.choice([1, 2, 5]))
        pk, pv, btab, qpos, q = _rand_case(rng, s=s, l=2, h=h, bs=bs,
                                           dk=dk, w=w, c=c)
        ref = _dense_ref(pk, pv, btab, qpos, q, 1)
        for force in ("lax", "interpret"):
            o = P.paged_attention(q, pk, pv, btab, qpos, layer=1,
                                  force=force)
            np.testing.assert_allclose(o, ref, rtol=5e-5, atol=5e-5)
        ck, sk = P.quantize_kv(pk, jnp.int8)
        cv, sv = P.quantize_kv(pv, jnp.int8)
        oq = P.paged_attention(q, ck, cv, btab, qpos, k_scale=sk,
                               v_scale=sv, layer=1, force="lax")
        rel = float(jnp.max(jnp.abs(oq - ref))
                    / jnp.max(jnp.abs(ref)))
        assert rel < 2e-2
