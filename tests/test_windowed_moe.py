"""A window bound in the flash kernels and the window-and-full
mixture-of-experts model on it (ISSUE 38), at small sizes with seeded
weights on the CPU: the windowed kernels in interpret mode against the
dense form, the walk's static cuts and what they count, the ops
"causal_attention" and "sigmoid_mul" and ``qk_norm_rope`` without its
rotation, the shares of a 16-way expert-parallel group adding up to the
uncut layer, and the whole small model against the benchmark's float32
reference (``chipbench/reference/afmoe_lm.py``).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.ops import causal_attention as CA
from paddle_tpu.ops import flash_attention as FA
from paddle_tpu.ops import rotary
from paddle_tpu.parallel import moe

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference import afmoe_lm  # noqa: E402


def _r(*shape, seed=0, scale=0.5):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


# -- the window bound through the flash kernels --------------------------------

def _band_written_out(q, k, v, h, hkv, window):
    """softmax(q k^T / sqrt(D)) v with `i - window < j <= i` written
    out, float32, query head a reading key/value head a // (h / hkv)."""
    b, t, hd = q.shape
    f32 = lambda x: x.astype(jnp.float32)
    qh = FA.heads_first(f32(q), h)
    kh, vh = (jnp.repeat(FA.heads_first(f32(x), hkv), h // hkv, 1)
              for x in (k, v))
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * (hd // h) ** -0.5
    s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
    return FA.heads_last(jnp.einsum("bhqk,bhkd->bhqd",
                                    jax.nn.softmax(s, -1), vh))


def _qkv(t, h, hkv, d, dtype, seed):
    mk = lambda n, s: _r(1, t, n * d, seed=s).astype(dtype)
    return mk(h, seed), mk(hkv, seed + 1), mk(hkv, seed + 2), mk(h, seed + 3)


# T 512 in streamed blocks of 128 (panels of 128) unless said otherwise
_WINDOWS = [
    (512, 128, 100, "under_a_tile"), (512, 128, 128, "one_block"),
    (512, 128, 200, "no_multiple_of_a_block"), (512, 128, 256, "two_blocks"),
    (512, 128, 511, "just_under_t"), (512, 128, 1, "its_own_key_alone"),
    (1024, 512, 300, "panels_of_256"), (512, None, 200, "all_of_t_one_block")]


@pytest.fixture(params=["fused_streamed", "two_kernels"])
def streamed_backward(request, monkeypatch):
    """What a streamed T's backward runs: the ONE kernel (ISSUE 39), or,
    its byte bound set to nothing, the two it replaced (a T too long
    for the bound keeps them)."""
    if request.param == "two_kernels":
        monkeypatch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)
    return {"fused_streamed": ["flash_bwd"],
            "two_kernels": ["flash_bwd_dq", "flash_bwd_dkv"]}[request.param]


@pytest.mark.parametrize("t, block, window", [w[:3] for w in _WINDOWS],
                         ids=[w[3] for w in _WINDOWS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_windowed_kernels_match_the_band_written_out(streamed_backward,
                                                     dtype, t, block,
                                                     window):
    """4 query heads of 128 reading ONE key/value head under a window,
    in interpret mode against dense float32 math with the band written
    out: out, dq, and dk, dv summed over the group; the dense form
    (the CPU path) beside them. Streamed, the forward and ONE backward
    kernel, or the two beyond its bound; all of T in one block, the
    fused backward."""
    h, hkv, d = 4, 1, 128
    q, k, v, dy = _qkv(t, h, hkv, d, dtype, seed=t + window)
    kw = dict(causal=True, block_q=block, block_k=block, n_kv_head=hkv,
              window=window)
    run = lambda q, k, v: FA.flash_bthd(q, k, v, h, force="interpret", **kw)
    dense = lambda q, k, v: FA.flash_bthd(q, k, v, h, force="dense", **kw)
    want = lambda q, k, v: _band_written_out(q, k, v, h, hkv, window)
    f32 = lambda x: x.astype(jnp.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    close = lambda name, a, b: np.testing.assert_allclose(
        f32(a), f32(b), atol=tol * max(float(jnp.max(jnp.abs(f32(b)))), 0.1),
        err_msg=name)
    o = run(q, k, v)
    assert o.shape == q.shape and o.dtype == dtype
    close("out", o, want(q, k, v))
    close("dense out", dense(q, k, v), want(q, k, v))
    loss = lambda fn: lambda *a: (f32(fn(*a)) * f32(dy)).sum()
    grad = jax.grad(loss(run), (0, 1, 2))
    names = [eqn.params["name"] for eqn in _pallas_eqns(
        jax.make_jaxpr(grad)(q, k, v).jaxpr)]
    assert names == ["flash_fwd"] + (["flash_bwd"] if block is None
                                     else streamed_backward)
    truth = jax.grad(loss(want), (0, 1, 2))(f32(q), f32(k), f32(v))
    for name, a, b, c in zip(("dq", "dk", "dv"), grad(q, k, v), truth,
                             jax.grad(loss(dense), (0, 1, 2))(q, k, v)):
        assert a.shape == b.shape and a.dtype == dtype
        close(name, a, b)
        close("dense " + name, c, b)


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


def test_the_grids_key_axis_holds_the_bands_steps_alone(streamed_backward):
    """T 2048 in blocks of 256 under a window of 512: a q block's band
    is its own block and the two before it, so the forward's grid is
    (heads, 8, 3) where causal's is (heads, 8, 8), the backward's
    (the ONE kernel's, by keys; the two kernels') likewise; with an lse
    output the same kernels."""
    h, t, d = 2, 2048, 128
    q = jnp.zeros((1, t, h * d), jnp.float32)
    grids = lambda **kw: [
        tuple(eqn.params["grid_mapping"].grid) for eqn in _pallas_eqns(
            jax.make_jaxpr(jax.grad(lambda q, k, v: FA.flash_bthd(
                q, k, v, h, causal=True, force="interpret", block_q=256,
                block_k=256, **kw).sum(), (0, 1, 2)))(q, q, q).jaxpr)]
    calls = 1 + len(streamed_backward)
    assert grids() == [(h, 8, 8)] * calls
    assert grids(window=512) == [(h, 8, 3)] * calls
    assert grids(window=514) == [(h, 8, 4)] * calls
    assert grids(window=2048) == [(h, 8, 8)] * calls       # plain causal
    o, lse = FA.flash_bthd_lse(_r(1, 512, h * d), _r(1, 512, h * d, seed=1),
                               _r(1, 512, h * d, seed=2), h, causal=True,
                               force="interpret", block_q=128, block_k=128,
                               window=130)
    _, want = FA.flash_bthd_lse(_r(1, 512, h * d), _r(1, 512, h * d, seed=1),
                                _r(1, 512, h * d, seed=2), h, causal=True,
                                force="dense", window=130)
    np.testing.assert_allclose(lse, want, atol=1e-5)


@pytest.mark.parametrize("t, block, tile, window, ratio", [
    (16384, 1024, 256, 2048, 1.125), (4096, 1024, 256, 2048, 1.1248),
    (16384, 1024, 1024, 2048, 1.5), (2048, 2048, 256, 512, 1.4996)],
    ids=["the_cell", "the_smoke_phase", "blocks_merely_masked", "one_block"])
def test_the_walks_cuts_count_what_they_compute(t, block, tile, window,
                                                ratio):
    """`band_scores` counts from the cuts `_walk` runs: by queries and
    by keys alike, never under the band's own count, and at the cell's
    shape 1.125 of it (ten sixteenths of the diagonal block and of the
    block on the lower edge, one block whole)."""
    computed, useful = FA.band_scores(t, block, tile, window)
    assert useful == sum(min(i + 1, window) for i in range(t))
    assert FA.band_scores(t, block, tile, window, True) == (computed, useful)
    assert computed >= useful
    assert round(computed / useful, 4) == ratio
    # every cut's segments lie inside the block, masked ones static
    for delta in range(FA._band_steps(window, block, t // block)):
        for mine, segments in FA._band_cuts(delta, block, tile,
                                            window) or []:
            assert 0 <= mine.start < mine.stop <= block
            for cols, off, how in segments:
                assert 0 <= cols.start < cols.stop <= block
                assert (off is None) == (how is None)


def test_a_window_counts_itself_and_composes_with_nothing_else():
    """`ptpu_flash_lowerings_total` carries the window ("0": none, and
    a window that holds all of T is none), `ptpu_flash_band_scores_total`
    the walk's scores where the kernels run; a mask in blocks, `strict`,
    `own_block`, a second part or no `causal` beside a window raise;
    unequal blocks go dense."""
    h, d, t = 2, 128, 512
    q, k, v, _ = _qkv(t, h, h, d, jnp.float32, seed=5)
    labels = dict(path="interpret", entry="bthd", heads_per_block="1",
                  backward="fused_streamed", mask="causal", kv_groups="1",
                  key_width="128", value_width="128", second_part="none")
    count = lambda w: FA._LOWERINGS.value(window=str(w), **labels)
    scores = lambda kind, walk="forward": FA._BAND_SCORES.value(
        window="200", walk=walk, kind=kind)
    walks = lambda: [scores("computed", "backward_by_" + by)
                     for by in ("queries", "keys")]
    was = count(200), count(0), scores("computed"), scores("useful")
    walked = walks()
    kw = dict(causal=True, force="interpret", block_q=128, block_k=128)
    FA.flash_bthd(q, k, v, h, window=200, **kw)
    FA.flash_bthd(q, k, v, h, window=t, **kw)
    FA.flash_bthd(q, k, v, h, **kw)
    assert (count(200), count(0)) == (was[0] + 1, was[1] + 2)
    computed, useful = FA.band_scores(t, 128, 128, 200)
    assert scores("computed") == was[2] + computed
    assert scores("useful") == was[3] + useful
    # the ONE streamed kernel walks by keys alone; the two kernels, for
    # a T over its bound, by queries too
    assert walks() == [walked[0], walked[1] + computed]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(FA, "_RESIDENT_DQ_BYTES", 0)
        FA.flash_bthd(q, k, v, h, window=200, **kw)
    assert walks() == [walked[0] + computed, walked[1] + 2 * computed]
    dense = dict(labels, path="dense", backward="none", window="200")
    before = FA._LOWERINGS.value(**dense)
    FA.flash_bthd(q, k, v, h, causal=True, force="interpret", block_q=256,
                  block_k=128, window=200)
    assert FA._LOWERINGS.value(**dense) == before + 1
    for bad in (dict(mask_block=4), dict(strict=True),
                dict(mask_block=4, own_block=True), dict(causal=False),
                dict(q2=q[..., :64 * h], k2=k[..., :64])):
        with pytest.raises(ValueError):
            FA.flash_bthd(q, k, v, h, **{**kw, "window": 200, **bad})


# -- the ops --------------------------------------------------------------------

def test_the_ops_scope_their_kernels_and_leave_the_rotation_out():
    """`causal_attention` names the kind of its layer under its op's
    scope (`window` / `full`); `qk_norm_rope` with `rotate` false is the
    QK-norm alone through `ops/rotary.norm_rope`; `sigmoid_mul` gates in
    float32 and hands back x's dtype."""
    h, hkv, d, t = 4, 2, 32, 64
    q, k, v, _ = _qkv(t, h, hkv, d, jnp.float32, seed=9)
    for window, scope in ((16, "window"), (0, "full")):
        text = str(jax.make_jaxpr(lambda q, k, v: CA.causal_attention(
            q, k, v, h, hkv, window))(q, k, v).pretty_print(
                name_stack=True))
        assert scope in text
        np.testing.assert_allclose(
            CA.causal_attention(q, k, v, h, hkv, window),
            _band_written_out(q, k, v, h, hkv, window or t), atol=2e-6)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [t, h * d], dtype="float32")
        g = fluid.layers.data("g", [t, h * d], dtype="float32")
        normed = fluid.layers.qk_norm_rope(x, h, rotate=False)
        turned = fluid.layers.qk_norm_rope(x, h)
        gated = fluid.layers.sigmoid_mul(x, g)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        got = exe.run(main, feed={"x": np.asarray(q), "g": np.asarray(k[
            ..., :1].repeat(h * d, -1))}, fetch_list=[normed, turned, gated])
    ones = jnp.ones(d)
    np.testing.assert_allclose(got[0], rotary.norm_rope(q, ones, h),
                               atol=1e-6)
    np.testing.assert_allclose(
        got[1], rotary.norm_rope(q, ones, h, 10000.0), atol=1e-6)
    assert float(np.max(np.abs(got[0] - got[1]))) > 0.1
    np.testing.assert_allclose(
        got[2], q * jax.nn.sigmoid(k[..., :1]), atol=1e-6)


# -- the shares ------------------------------------------------------------------

def test_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """16 chips holding 2 of 32 experts each, sigmoid top-4 with
    `route_scale`: the program's routed outputs of the sixteen shares
    plus ONE shared expert equal the reference's layer that holds all
    32, and each share is the reference's own share."""
    n, d, f, e, k_top, held = 48, 16, 12, 32, 4, 2
    x, wr = _r(n, d, seed=70, scale=1.0), _r(d, e, seed=71)
    wg, wu = (_r(e, d, f, seed=s, scale=d ** -0.5) for s in (72, 73))
    wd = _r(e, f, d, seed=74, scale=f ** -0.5)
    shared = tuple(_r(*shape, seed=s, scale=0.3) for shape, s in (
        ((d, f), 75), ((d, f), 76), ((f, d), 77)))
    cfg = {"num_experts_per_tok": k_top, "published": {"num_experts": e},
           "route_norm": True, "route_scale": 2.826}
    mm = lambda a, b: a @ b

    def reference(first, count, with_shared):
        p = {"router": wr, "bias": jnp.zeros(e), "shared": shared,
             "w_gate": wg[first:first + count],
             "w_up": wu[first:first + count],
             "w_down": wd[first:first + count]}
        return afmoe_lm.expert_layer(p, x, cfg, first, count, mm,
                                     shared=with_shared)

    routed = lambda first: moe.routed_experts(
        x, wr, wg[first:first + held], wu[first:first + held],
        wd[first:first + held], e, first, k_top, True, score="sigmoid",
        scaling=2.826, shared_expert=True)[0]
    once = (jax.nn.silu(x @ shared[0]) * (x @ shared[1])) @ shared[2]
    shares = [routed(first) for first in range(0, e, held)]
    assert len(shares) == 16
    np.testing.assert_allclose(once + sum(shares), reference(0, e, True),
                               atol=3e-5)
    np.testing.assert_allclose(shares[3], reference(6, held, False),
                               atol=3e-5)
    # the shared expert once, and not a sixteenth of it, nor sixteen
    assert float(jnp.max(jnp.abs(once))) > 1e-2


# -- the whole small model against the benchmark's reference -------------------

CFG = {"arch": "afmoe", "vocab_size": 96, "num_hidden_layers": 4,
       "num_dense_layers": 1, "hidden_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 12,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention"],
       "intermediate_size": 40, "moe_intermediate_size": 24,
       "num_experts": 4, "published": {"num_experts": 8}, "first_expert": 2,
       "num_experts_per_tok": 2, "num_shared_experts": 1,
       "route_norm": True, "route_scale": 2.826,
       "load_balance_coeff": 1e-3, "rope_theta": 10000,
       "rms_norm_eps": 1e-5, "embedding_init_std": 0.02,
       "router_init_std": 0.1}
SEQ = 32


def _small_model():
    from chipbench import cells
    arch = cells.load_arch("afmoe")
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        cost, logits = arch.build(CFG, SEQ)
        forward = main.clone(for_test=True)
    return arch, main, startup, forward, scope, cost, logits


def _batch(rows=2):
    rng = np.random.RandomState(12)
    src = rng.randint(3, 96, (rows, SEQ)).astype(np.int64)
    return {"src": src, "label": np.roll(src, -1, axis=1),
            "mask": (rng.rand(rows, SEQ) > 0.2).astype(np.float32)}


def test_small_model_loss_and_logits_are_the_references():
    """The for_test clone's loss and logits, with every routed layer's
    choices fetched from INSIDE its recompute region in the same run;
    the stack's kinds as the program's ops state them."""
    arch, main, startup, forward, scope, cost, logits = _small_model()
    feed = _batch()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        params = arch.params_of_program(main, scope, CFG)
        names = arch.router_choices(forward)
        fetched = exe.run(forward, feed=feed,
                          fetch_list=[cost, logits] + list(names))
        counters = arch.program_counters(main, scope)
    ops = arch._ops(forward)
    assert [op.attr("window") for op in ops
            if op.type == "causal_attention"] == [12, 12, 0, 12]
    assert [op.attr("rotate", True) for op in ops
            if op.type == "qk_norm_rope"] == [True, True, True, True, False,
                                              False, True, True]
    assert sum(op.type == "rms_norm" for op in ops) == 4 * 4 + 1
    assert sum(op.type == "sigmoid_mul" for op in ops) == 4
    got_cost, got_logits, choices = fetched[0], fetched[1], fetched[2:]
    want = arch.lm_loss(params, feed["src"], feed["label"], feed["mask"], CFG)
    np.testing.assert_allclose(got_cost, want, rtol=2e-5)
    assert len(choices) == 3 and choices[0].shape == (2, SEQ, 2)
    # a for_test run counts nothing and moves no bias
    assert counters["steps"] == [0] and sum(counters["expert_rows"]) == 0
    for row in range(2):
        ref = arch.logits_at(params, jnp.asarray(feed["src"][row]), 0, SEQ,
                             CFG)
        np.testing.assert_allclose(got_logits[row], ref, atol=3e-5)
        handed = arch.logits_at(
            params, jnp.asarray(feed["src"][row]), 0, SEQ, CFG,
            np.stack([c[row:row + 1] for c in choices]))
        np.testing.assert_allclose(handed, ref, atol=1e-6)


def test_small_model_one_steps_gradients_are_the_references():
    """SGD at rate 1 turns a step's parameter change into its gradient:
    every parameter's against jax.grad of the reference's loss, through
    the recompute regions; the counts, the step counter and the
    selection bias move ONCE a step."""
    arch, main, startup, _, scope, cost, _ = _small_model()
    feed = _batch()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        fluid.optimizer.SGD(learning_rate=1.0).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        before = arch.params_of_program(main, scope, CFG)
        exe.run(main, feed=feed, fetch_list=[cost])
        after = arch.params_of_program(main, scope, CFG)
        counters = arch.program_counters(main, scope)
    assert counters["steps"] == [1]
    # three routed layers x 64 rows x top-2, once a step
    assert sum(counters["expert_rows"]) == 3 * 2 * SEQ * 2
    for layer in after["layers"][1:]:
        np.testing.assert_allclose(np.abs(layer["bias"]), 1e-3, rtol=1e-5)

    def floats(p):
        return {**p, "layers": [{k: v for k, v in layer.items()
                                 if k != "bias"} for layer in p["layers"]]}

    def loss(p):
        whole = {**p, "layers": [
            {**layer, **({"bias": was["bias"]} if "bias" in was else {})}
            for layer, was in zip(p["layers"], before["layers"])]}
        return arch.lm_loss(whole, feed["src"], feed["label"], feed["mask"],
                            CFG)

    grads = jax.grad(loss)(floats(before))
    moved = jax.tree.map(lambda a, b: a - b, floats(before), floats(after))
    flat_g, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_m = jax.tree.leaves(moved)
    # embedding, final norm, head; a layer: 11 of attention and norms,
    # 3 of the dense FFN or 3 + 4 of experts
    assert len(flat_g) == 3 + 4 * 11 + 3 + 3 * 7
    for (path, g), m in zip(flat_g, flat_m):
        scale = float(np.max(np.abs(g))) + 1e-8
        assert float(np.max(np.abs(g - m))) / scale < 2e-3, \
            jax.tree_util.keystr(path)
