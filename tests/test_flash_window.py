"""A window bound in the flash kernels (ISSUE 38), in interpret mode on
the CPU: the windowed kernels against the band written out and against
the dense form, the grid's key axis, the walk's static cuts and what
they count, the lowering's labels. One file of the flash kernels'
family (tests/flash_test.py holds what they share); the model that
runs on the window is tests/test_windowed_moe.py's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import flash_attention as FA
from flash_test import (_assert_within, _band_inputs, _band_written_out,
                        _host32, _pallas_eqns, _traced_once, _with_grads)


def _r(*shape, seed=0, scale=0.5):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


# T 256 in four streamed blocks of 64 (one panel each) unless said
# otherwise: four, so that a band of one, two or three blocks leaves a
# block below it unvisited
_WINDOWS = [
    (256, 64, 50, "under_a_tile"), (256, 64, 64, "one_block"),
    (256, 64, 100, "no_multiple_of_a_block"), (256, 64, 128, "two_blocks"),
    (256, 64, 255, "just_under_t"), (256, 64, 1, "its_own_key_alone"),
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


@functools.lru_cache(maxsize=None)
def _band_sides(dtype, t, block, window):
    """((q, k, v), the loss's weights dy, (out, gradients) of the band
    written out in float32, the same of the dense form, the CPU path):
    one compiled program each, made once for both backwards."""
    h, hkv, d = 4, 1, 128
    q, k, v, dy = _band_inputs(t, h, hkv, d, dtype, seed=t + window)
    kw = dict(causal=True, block_q=block, block_k=block, n_kv_head=hkv,
              window=window)
    weigh = lambda o: (o.astype(jnp.float32) * dy.astype(jnp.float32)).sum()
    want = jax.jit(_with_grads(
        lambda q, k, v: _band_written_out(q, k, v, h, hkv, window),
        weigh))(*_host32(q, k, v))
    dense = jax.jit(_with_grads(
        lambda q, k, v: FA.flash_bthd(q, k, v, h, force="dense", **kw),
        weigh))(q, k, v)
    return (q, k, v), weigh, kw, want, dense


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
    h = 4
    (q, k, v), weigh, kw, want, dense = _band_sides(dtype, t, block, window)
    run = lambda q, k, v: FA.flash_bthd(q, k, v, h, force="interpret", **kw)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    close = functools.partial(_assert_within, tol=tol)
    # the kernels' out and gradients as one program, traced once
    eqns, (o, grads) = _traced_once(_with_grads(run, weigh), q, k, v)
    assert o.shape == q.shape and o.dtype == dtype
    close("out", o, want[0])
    close("dense out", dense[0], want[0])
    assert [eqn.params["name"] for eqn in eqns] == ["flash_fwd"] + (
        ["flash_bwd"] if block is None else streamed_backward)
    if block:     # the forward's key axis holds the band's steps alone
        assert tuple(eqns[0].params["grid_mapping"].grid) == (
            h, t // block, min(-(-(window - 1) // block) + 1, t // block))
    for name, a, b, c in zip(("dq", "dk", "dv"), grads, want[1], dense[1]):
        assert a.shape == b.shape and a.dtype == dtype
        close(name, a, b)
        close("dense " + name, c, b)


@pytest.mark.parametrize("window", [100, None],
                         ids=["window_100", "no_window"])
def test_a_group_of_seven_query_heads_reads_its_own_kv_head(
        streamed_backward, window):
    """ISSUE 46: 14 query heads of 128 reading TWO key/value heads,
    query head j the head j // 7, T 256 in four streamed blocks of 64
    (a window of 100 cuts a block), in interpret mode against dense
    float32 math with the mask written out: out, dq, and dk, dv summed
    over each group of seven, through the ONE backward kernel and
    through the two. A kernel that read head j % 2 would pass no
    tolerance here."""
    h, hkv, d, t, block = 14, 2, 128, 256, 64
    q, k, v, dy = _band_inputs(t, h, hkv, d, jnp.float32, seed=46)
    weigh = lambda o: (o * dy).sum()
    want = jax.jit(_with_grads(
        lambda q, k, v: _band_written_out(q, k, v, h, hkv, window or t),
        weigh))(q, k, v)
    run = lambda q, k, v: FA.flash_bthd(
        q, k, v, h, force="interpret", causal=True, block_q=block,
        block_k=block, n_kv_head=hkv, window=window)
    was = FA._LOWERINGS.snapshot()
    eqns, (o, grads) = _traced_once(_with_grads(run, weigh), q, k, v)
    assert [eqn.params["name"] for eqn in eqns] == ["flash_fwd"] \
        + streamed_backward
    (key,) = [key for key, n in FA._LOWERINGS.snapshot().items()
              if n != was.get(key, 0)]
    label = dict(zip(FA._LOWERINGS.label_names, key))
    assert (label["path"], label["kv_groups"], label["window"]) == (
        "interpret", "7", str(window or 0))
    _assert_within("out", o, want[0], tol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), grads, want[1]):
        assert a.shape == b.shape
        _assert_within(name, a, b, tol=2e-5)
    # the other mapping is far off: the groups are told apart
    other = _band_written_out(q, jnp.roll(k.reshape(1, t, hkv, d), 1, 2
                                          ).reshape(k.shape), v, h, hkv,
                              window or t)
    assert float(jnp.max(jnp.abs(other - want[0]))) > 0.05


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
    q, k, v, _ = _band_inputs(t, h, h, d, jnp.float32, seed=5)
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
