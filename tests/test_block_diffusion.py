"""The pre-norm block's ops, the dropless expert layer and the
block-diffusion objective (ISSUE 32), at small sizes with seeded
weights on the CPU: each op against ``jax.numpy``, the expert layer
against a dense loop over experts, the attention of [noised; clean]
rows against the 2L x 2L mask written out, and the whole small model
against the
benchmark's float32 reference (``chipbench/reference/sdar_lm.py``).
The ops: "rms_norm", "rope", "silu_mul", "block_diffusion_noise",
"block_diffusion_attention", "routed_experts", and (ISSUE 33)
"qk_norm_rope" with the kernel pair of ``ops/rotary.py`` in interpret
mode against the reference's ``_rms`` and ``_rope``.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import small_model_test
from flash_test import _with_grads
from op_test import check_grad, check_output, run_op
from paddle_tpu.ops import block_diffusion as BD
from paddle_tpu.ops import rotary
from paddle_tpu.parallel import moe

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference import sdar_lm  # noqa: E402


def _r(*shape, seed=0, scale=0.5):
    return (np.random.RandomState(seed).randn(*shape) * scale).astype(
        np.float32)


# -- the ops ------------------------------------------------------------------

@pytest.mark.parametrize("groups", [1, 4], ids=["whole", "qk_norm"])
def test_rms_norm_op(groups):
    x, w = _r(2, 6, 32, seed=1), 1.0 + _r(32 // groups, seed=2, scale=0.1)
    g = x.reshape(2, 6, groups, -1)
    want = g / np.sqrt(np.mean(g * g, -1, keepdims=True) + 1e-6) * w
    check_output("rms_norm", {"X": x, "Scale": w}, {"epsilon": 1e-6},
                 {"Out": want.reshape(x.shape)}, rtol=1e-5)
    check_grad("rms_norm", {"X": x, "Scale": w}, {"epsilon": 1e-6},
               ["X", "Scale"])


@pytest.mark.parametrize("wrap", [0, 4], ids=["by_index", "two_halves"])
def test_rope_op(wrap):
    """Against the reference's own rotate-half RoPE, and the row at
    position 0 comes back as it went in."""
    x = _r(2, 8, 4 * 16, seed=3)
    pos = np.arange(8) % wrap if wrap else np.arange(8)
    want = np.stack([np.asarray(sdar_lm._rope(
        jnp.asarray(row).reshape(8, 4, 16), jnp.asarray(pos), 1e6)).reshape(
            8, 64) for row in x])
    attrs = {"n_head": 4, "theta": 1e6, "wrap": wrap}
    got = run_op("rope", {"X": x}, attrs, ["Out"])["Out"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)
    if wrap:
        assert not np.allclose(got[:, 1], got[:, 5])   # other rows, same turn
    check_grad("rope", {"X": x}, attrs, ["X"])


# -- QK-norm and RoPE in the projections' own layout (ISSUE 33) ---------------

def _reference_norm_rope(x, w, n_head, wrap, norm=True, rotate=True):
    """The benchmark reference's ``_rope(_rms(x))`` a sequence at a
    time, on the heads' view it works in."""
    b, t, hd = x.shape
    pos = jnp.arange(t) % wrap if wrap else jnp.arange(t)
    rows = []
    for row in x:
        y = row.reshape(t, n_head, hd // n_head)
        if norm:
            y = sdar_lm._rms(y, w, 1e-6)
        if rotate:
            y = sdar_lm._rope(y, pos, 1e6)
        rows.append(y.reshape(t, hd))
    return jnp.stack(rows)


def _largest(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


@functools.partial(jax.jit, static_argnums=0)
def _out_and_grads(fn, x, w, dy):
    """fn(x, w) and dy's pull-back to (x, w) as ONE program: the
    reference walks the heads in Python, an op at a time eagerly."""
    out, pull = jax.vjp(fn, x, w)
    return out, pull(dy)


# L 136: 272 rows a sequence, which the 256 rows that 1 MB of float32
# holds at 1024 lanes do not divide: a block is the period's divisor, 136.
# Heads of 64 sit two to a lane tile in the same kernels (ISSUE 50)
@pytest.mark.parametrize("wrap", [0, 136], ids=["by_index", "two_halves"])
@pytest.mark.parametrize("n_head, d, dtype", [
    (32, 128, "float32"), (4, 128, "float32"), (32, 64, "float32"),
    (8, 64, "float32"), (2, 64, "float32"), (32, 64, "bfloat16"),
    (8, 64, "bfloat16")],
    ids=["q_heads", "kv_heads", "q_heads_of_64", "kv_heads_of_64",
         "one_tile_of_64", "q_heads_of_64_bf16", "kv_heads_of_64_bf16"])
def test_norm_rope_kernel_is_the_reference(n_head, d, dtype, wrap):
    """The kernel pair in interpret mode: the output and the gradients
    of x and of Scale against the reference's `_rms` and `_rope` (dense
    float32 math on the heads' view) and against the jax.numpy form;
    under `wrap` the gradient passes through the shared positions: the
    clean half's rows turn as the noised half's do. bfloat16 rounds the
    output and dx alone: float32 inside, Scale's gradient in Scale's
    dtype."""
    # bfloat16's sublane tiles hold 16 rows: twice the rows all through
    rows_of = 2 if dtype == "bfloat16" else 1
    t, wrap = 272 * rows_of, wrap * rows_of
    x, dy = (jnp.asarray(_r(2, t, n_head * d, seed=seed)).astype(dtype)
             for seed in (21, 23))
    w = jnp.asarray(1.0 + _r(d, seed=22, scale=0.1))
    rows, lanes = rotary._blocks(2 * t, rotary._period(t, wrap), n_head * d,
                                 d, x.dtype.itemsize)
    # at most eight heads to a block, and 1024 lanes
    assert lanes == min(n_head, 8) * d
    assert rows == rows_of * (136 if wrap or lanes == 1024 else 272)
    run = lambda force: lambda x, w: rotary.norm_rope(
        x, w, n_head, 1e6, wrap, 1e-6, force=force)
    f32 = lambda v: v.astype(jnp.float32)
    want, pulled = _out_and_grads(
        lambda x, w: _reference_norm_rope(x, w, n_head, wrap), f32(x), w,
        f32(dy))
    out_tol, dx_tol = (1e-6, 2e-6) if dtype == "float32" else (2 ** -7,) * 2
    for force in ("interpret", "xla"):
        got, grads = _out_and_grads(run(force), x, w, dy)
        assert got.dtype == grads[0].dtype == x.dtype
        assert _largest(got, want) < out_tol
        assert _largest(grads[0], pulled[0]) < dx_tol
        assert _largest(grads[1], pulled[1]) < 2e-6
    if wrap:
        twice = jnp.concatenate([x[:, :wrap]] * 2, 1)
        got, (dx, _) = _out_and_grads(
            run("interpret"), twice, w,
            jnp.concatenate([dy[:, :wrap]] * 2, 1))
        np.testing.assert_array_equal(got[:, :wrap], got[:, wrap:])
        np.testing.assert_array_equal(dx[:, :wrap], dx[:, wrap:])


@pytest.mark.parametrize("norm, rotate", [(True, False), (False, True)],
                         ids=["norm_alone", "rope_alone"])
@pytest.mark.parametrize("n_head, d, wrap", [
    (4, 128, 16), (2, 64, 0), (8, 64, 16), (32, 64, 16)],
    ids=["heads_of_128", "one_tile_of_64", "kv_heads_of_64",
         "q_heads_of_64"])
def test_norm_rope_kernel_serves_each_op_alone(n_head, d, wrap, norm, rotate):
    """One flag off: the grouped `rms_norm` and `rope` lower to the same
    kernel body; bfloat16 in, bfloat16 out, float32 inside."""
    t = 32
    x = jnp.asarray(_r(2, t, n_head * d, seed=24))
    w = jnp.asarray(1.0 + _r(d, seed=25, scale=0.1))
    dy = jnp.asarray(_r(2, t, n_head * d, seed=26))
    args = (n_head, 1e6 if rotate else None, wrap, 1e-6)
    run = lambda x, w: rotary.norm_rope(x, w if norm else None, *args,
                                        force="interpret")
    want, pulled = _out_and_grads(lambda x, w: _reference_norm_rope(
        x, w, n_head, wrap, norm, rotate), x, w, dy)
    got, (dx, dw) = _out_and_grads(run, x, w, dy)
    assert _largest(got, want) < 1e-6
    assert _largest(dx, pulled[0]) < 2e-6
    assert _largest(dw, pulled[1]) < 2e-6 if norm else not dw.any()
    half, (dx, dw) = _out_and_grads(run, x.astype(jnp.bfloat16), w,
                                    dy.astype(jnp.bfloat16))
    assert half.dtype == dx.dtype == jnp.bfloat16
    assert _largest(half.astype(jnp.float32), want) < 2 ** -7
    assert _largest(dx.astype(jnp.float32), pulled[0]) < 2 ** -6
    assert _largest(dw, pulled[1]) < 2 ** -7 if norm else not dw.any()


def test_qk_norm_rope_op_is_rope_of_rms_norm():
    """The fused op against the two ops one after the other, and its
    gradients against finite differences."""
    x, w = _r(2, 8, 4 * 16, seed=27), 1.0 + _r(16, seed=28, scale=0.1)
    attrs = {"n_head": 4, "theta": 1e6, "wrap": 4, "epsilon": 1e-6}
    normed = run_op("rms_norm", {"X": x, "Scale": w}, {"epsilon": 1e-6},
                    ["Out"])["Out"]
    want = run_op("rope", {"X": normed},
                  {k: attrs[k] for k in ("n_head", "theta", "wrap")},
                  ["Out"])["Out"]
    check_output("qk_norm_rope", {"X": x, "Scale": w}, attrs, {"Out": want},
                 rtol=1e-5, atol=1e-6)
    check_grad("qk_norm_rope", {"X": x, "Scale": w}, attrs, ["X", "Scale"])


@pytest.mark.parametrize("d", [128, 64])
def test_rotary_lowering_counter_says_which_path_engaged(d):
    """`ptpu_rotary_lowerings_total{path, heads, head_dim, norm,
    rotate}`: one count a lowering, whichever path: the kernel in
    interpret mode, the jax.numpy form off the chip (what the Program's
    ops take on the CPU, at any head size), and `pallas` never here."""
    count = rotary._LOWERINGS
    x, w = jnp.asarray(_r(1, 16, 2 * d, seed=29)), jnp.ones(d)
    for force, path, scale, theta in (
            ("interpret", "interpret", w, 1e6), (None, "xla", w, 1e6),
            (None, "xla", w, None), ("interpret", "interpret", None, 1e6)):
        labels = dict(path=path, heads="2", head_dim=str(d),
                      norm=str(scale is not None).lower(),
                      rotate=str(theta is not None).lower())
        was = count.value(**labels)
        rotary.norm_rope(x, scale, 2, theta, force=force)
        assert count.value(**labels) == was + 1
    labels = dict(path="xla", heads="4", head_dim="16", norm="true",
                  rotate="true")
    was = count.value(**labels)
    run_op("qk_norm_rope", {"X": _r(2, 8, 64), "Scale": np.ones(16, "f")},
           {"n_head": 4, "theta": 1e6, "wrap": 4, "epsilon": 1e-6}, ["Out"])
    assert count.value(**labels) == was + 1
    rendered = fluid.monitor.metrics.registry().render_prometheus()
    assert "ptpu_rotary_lowerings_total" in rendered
    assert 'ptpu_rotary_lowerings_total{path="pallas"' not in rendered


def test_heads_that_are_not_whole_lane_tiles_keep_the_jax_numpy_form(
        monkeypatch):
    """An odd number of heads of 64 and a single one (MLA's rotary key)
    are not whole lane tiles: the jax.numpy form under auto, counted as
    such, nothing raised; the kernel forced there says why not. On a
    TPU the shape alone says which path (ISSUE 50)."""
    count = rotary._LOWERINGS
    for heads in (3, 1):
        labels = dict(path="xla", heads=str(heads), head_dim="64",
                      norm="false", rotate="true")
        was = count.value(**labels)
        part = jnp.asarray(_r(1, 16, heads * 64, seed=30))
        want = _reference_norm_rope(part, None, heads, 0, norm=False)
        assert _largest(rotary.norm_rope(part, None, heads, 1e6), want) < 1e-6
        assert count.value(**labels) == was + 1
        with pytest.raises(ValueError):
            rotary.norm_rope(part, None, heads, 1e6, force="interpret")
    monkeypatch.setattr(rotary, "_on_tpu", lambda x: True)
    for heads, d, path in (
            (2, 128, "pallas"), (32, 64, "pallas"), (8, 64, "pallas"),
            (3, 64, "xla"), (1, 64, "xla"), (2, 96, "xla"), (2, 192, "xla")):
        rows, lanes = rotary._blocks(32, 32, heads * d, d, 2)
        assert lanes % 128 == 0 or path == "xla"
        assert rotary._resolve_path(jnp.zeros((1, 32, heads * d)), d, rows,
                                    True, None) == path


def test_silu_mul_op():
    a, b = _r(3, 5, 8, seed=4), _r(3, 5, 8, seed=5)
    check_output("silu_mul", {"X": a, "Y": b}, {},
                 {"Out": a / (1.0 + np.exp(-a)) * b}, rtol=1e-5)
    check_grad("silu_mul", {"X": a, "Y": b}, {}, ["X", "Y"])


def test_noise_op_is_the_references_at_steps_0_and_1():
    """The draw is a function of (salt, step, batch row): the op gives
    the reference's ``noise`` at the step its counter holds, advances
    the counter in a train run and leaves it in a `for_test` run."""
    tokens = np.random.RandomState(6).randint(3, 50, (3, 32)).astype(np.int64)
    drawn = []
    for step in (0, 1):
        for is_test in (False, True):
            got = run_op("block_diffusion_noise",
                         {"X": tokens, "Salt": np.array([77], np.int32),
                          "Step": np.array([step], np.int32)},
                         {"block": 4, "mask_id": 0},
                         ["Noised", "Weight"] + ([] if is_test
                                                 else ["StepOut"]),
                         is_test=is_test)
            masked, t = sdar_lm.noise(77, step, 3, 32, 4)
            np.testing.assert_array_equal(
                got["Noised"], np.where(masked, 0, tokens))
            np.testing.assert_allclose(
                got["Weight"], np.where(masked, 1.0 / t, 0.0), rtol=1e-6)
            if not is_test:
                assert got["StepOut"].tolist() == [step + 1]
        # one t a block of 4, within (1e-3, 1)
        t = np.asarray(t).reshape(3, 8, 4)
        assert (t == t[..., :1]).all() and (t > 1e-3).all() and (t < 1).all()
        drawn.append(got["Noised"])
    assert (drawn[0] != drawn[1]).any()          # fresh noise a step
    assert (run_op("block_diffusion_noise",
                   {"X": tokens, "Salt": np.array([78], np.int32),
                    "Step": np.array([0], np.int32)},
                   {"block": 4, "mask_id": 0},
                   ["Noised"])["Noised"] != drawn[0]).any()


# -- attention ----------------------------------------------------------------

def _dense_bd_attention(q, k, v, n_head, n_kv_head, block):
    """The 2L x 2L mask written out (the reference's ``bd_mask``)."""
    b, t2, hd = q.shape
    d = hd // n_head
    mask = sdar_lm.bd_mask(t2 // 2, block)
    split = lambda x, h: x.reshape(b, t2, h, d).transpose(0, 2, 1, 3)
    qh = split(q, n_head)
    kh, vh = (jnp.repeat(split(x, n_kv_head), n_head // n_kv_head, 1)
              for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * d ** -0.5
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vh).transpose(
        0, 2, 1, 3).reshape(b, t2, hd)


@pytest.mark.parametrize("force", ["dense", "interpret"])
@pytest.mark.parametrize("block", [4, 32])
def test_two_piece_attention_is_the_dense_mask(block, force):
    """The flash kernels' own-block form (until ISSUE 37 two flash
    pieces merged by lse with the noised rows' own blocks as dense
    math) against the mask written out, forward and gradients; the
    first block's noised rows, which see no clean key, come out as
    their own block's attention alone."""
    h, hkv, d, seq = 4, 2, 128, 128
    q = jnp.asarray(_r(1, 2 * seq, h * d, seed=7))
    k, v = (jnp.asarray(_r(1, 2 * seq, hkv * d, seed=s)) for s in (8, 9))
    dy = jnp.asarray(_r(1, 2 * seq, h * d, seed=10))
    got = lambda q, k, v: BD.attention(q, k, v, h, hkv, block, force=force)
    want = lambda q, k, v: _dense_bd_attention(q, k, v, h, hkv, block)
    # each side's output and gradients as ONE program: eagerly the
    # mask written out and its transpose compile an op at a time
    (o_got, g_got), (o_want, g_want) = (
        jax.jit(_with_grads(f, lambda o: (o * dy).sum()))(q, k, v)
        for f in (got, want))
    np.testing.assert_allclose(o_got, o_want, atol=2e-5)
    for name, a, b in zip("qkv", g_got, g_want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)


# -- the expert layer ---------------------------------------------------------

N, D, F, E, HELD, K = 96, 32, 16, 16, 4, 4


def _experts(seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)
    return mk(N, D), mk(D, E), mk(E, D, F), mk(E, D, F), mk(E, F, D)


def _dense_experts(x, wr, wg, wu, wd, first, held, act=jax.nn.silu,
                   dtype=jnp.float32):
    """Every expert of the share on every row, in float32; `dtype`: what
    the layer rounds the experts' input to (its weights' dtype)."""
    _, w, idx = moe.route(x, wr, K, True)
    x = x.astype(dtype).astype(jnp.float32)
    out = 0.0
    for e in range(first, first + held):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), 1)
        out = out + w_e[:, None] * ((act(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return out


def _held(x, wr, wg, wu, wd, first, held, activation="silu",
          dtype=jnp.float32):
    return moe.routed_experts(
        x, wr, *(w[first:first + held].astype(dtype) for w in (wg, wu, wd)),
        E, first, K, True, activation=activation)[0]


def _one_sided(x, wr):
    """Every row chooses experts 4..7, all held by the share at 4."""
    return x.at[:, 0].set(1.0), (wr * 0.01).at[0, 4:8].set(50.0)


@pytest.mark.parametrize("gate", ["silu", "relu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("routing", ["uniform", "all_on_held", "two_chunks",
                                     "no_held_pair"])
def test_expert_layer_is_the_dense_loop(routing, dtype, gate):
    """Output and every gradient (x, router, the three weights) of the
    written backward (ISSUE 47) against `jax.grad` of a dense loop over
    the held experts, float32 and with bfloat16 experts (whose weights
    both sides round alike; the layer then rounds the hidden activations
    and the cotangents where they enter a grouped matmul, the dense loop
    nothing). `all_on_held`: every row on held experts, nothing dropped,
    one full chunk. `two_chunks`: twice the rows, so the pairs pass one
    chunk and the loop over chunks runs twice (chunk 0's gradients plus
    an added chunk's). `no_held_pair`: the share at 8 holds no chosen
    expert: zeros, exactly."""
    x, wr, wg, wu, wd = _experts()
    first = 4
    if routing == "two_chunks":
        x = jnp.concatenate([x, _experts(seed=1)[0]])
    if routing != "uniform":
        x, wr = _one_sided(x, wr)
    if routing == "no_held_pair":
        first = 8
    wg, wu, wd = (w.astype(dtype).astype(jnp.float32) for w in (wg, wu, wd))
    args = (x, wr, wg, wu, wd)
    n = x.shape[0]
    _, _, counts, _ = moe.routed_experts(
        x, wr, wg[first:first + 4], wu[first:first + 4],
        wd[first:first + 4], E, first, K, True)
    assert int(counts.sum()) == n * K
    if routing != "uniform":
        assert counts.tolist() == [0] * 4 + [n] * 4 + [0] * 8
    if routing == "two_chunks":     # 768 pairs in chunks of 512
        assert n * K > -(-2 * n * K * HELD // E // 512) * 512
    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[gate]
    held = functools.partial(_held, activation=gate, dtype=dtype)
    dense = functools.partial(_dense_experts, act=act, dtype=dtype)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    want_out = dense(*args, first, HELD)
    np.testing.assert_allclose(
        held(*args, first, HELD), want_out,
        atol=1e-5 if dtype == jnp.float32
        else tol * float(jnp.max(jnp.abs(want_out))) + 1e-30)
    sq = lambda f: lambda *a: (f(*a, first, HELD) ** 2).sum()
    # each as ONE program: eagerly the layer's loop, the dense loop over
    # the experts and their transposes are compiled an op at a time
    got = jax.jit(jax.grad(sq(held), (0, 1, 2, 3, 4)))(*args)
    want = jax.jit(jax.grad(sq(dense), (0, 1, 2, 3, 4)))(*args)
    for name, a, b in zip(("x", "router", "gate", "up", "down"), got, want):
        if routing == "no_held_pair":
            assert float(jnp.max(jnp.abs(a))) == 0.0 == float(
                jnp.max(jnp.abs(b))), name
            continue
        # the layer's share of the weights' gradients: the held experts'
        if name in ("gate", "up", "down"):
            assert float(jnp.max(jnp.abs(a[:first]))) == 0.0, name
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale < tol, name


@pytest.mark.parametrize("routing", ["uniform", "all_on_held"])
def test_the_shares_add_up(routing):
    """The 4 shares of 4 experts sum to the uncut layer's output: no
    share computes another's experts or leaves one of its own out."""
    x, wr, wg, wu, wd = _experts(seed=3)
    if routing == "all_on_held":
        x, wr = _one_sided(x, wr)
    shares = sum(_held(x, wr, wg, wu, wd, first, HELD)
                 for first in range(0, E, HELD))
    np.testing.assert_allclose(
        shares, _dense_experts(x, wr, wg, wu, wd, 0, E), atol=1e-5)


def test_aux_loss_and_lowering_counter():
    x, wr, wg, wu, wd = _experts(seed=5)
    labels = dict(path="ragged_dot", experts=str(E), experts_held=str(HELD),
                  top_k=str(K), score="softmax", shared_expert="false",
                  rows="xla", activation="silu", router_input="own")
    was = moe._LOWERINGS.value(**labels)
    _, aux, counts, idx = moe.routed_experts(x, wr, wg[:4], wu[:4], wd[:4],
                                             E, 0, K, True)
    assert moe._LOWERINGS.value(**labels) == was + 1
    probs = jax.nn.softmax(x @ wr, -1)
    np.testing.assert_allclose(
        aux, E * jnp.sum(counts / N * jnp.mean(probs, 0)), rtol=1e-5)
    assert idx.shape == (N, K) and len(set(idx[0].tolist())) == K


def test_routed_experts_op_counts_train_runs_only():
    x, wr, wg, wu, wd = (np.asarray(a) for a in _experts(seed=7))
    ins = {"X": x.reshape(2, N // 2, D), "RouterW": wr, "WGate": wg[:4],
           "WUp": wu[:4], "WDown": wd[:4], "Load": np.ones(E, np.int32)}
    attrs = {"first_expert": 0, "top_k": K, "norm_topk": True}
    got = run_op("routed_experts", ins, attrs,
                 ["Out", "AuxLoss", "Indices", "LoadOut"])
    np.testing.assert_allclose(
        got["Out"].reshape(N, D),
        _dense_experts(*(jnp.asarray(a) for a in (x, wr, wg, wu, wd)), 0, 4),
        atol=1e-5)
    assert got["Indices"].shape == (2, N // 2, K)
    assert int(got["LoadOut"].sum()) == E + N * K
    test = run_op("routed_experts", ins, attrs, ["Out"], is_test=True)
    np.testing.assert_allclose(test["Out"], got["Out"], atol=1e-6)


# -- the whole small model against the benchmark's reference -------------------

CFG = {"vocab_size": 96, "num_hidden_layers": 2, "hidden_size": 32,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "moe_intermediate_size": 24, "num_experts": 4, "first_expert": 4,
       "published": {"num_experts": 16}, "num_experts_per_tok": 4,
       "norm_topk_prob": True, "block_length": 4, "mask_token_id": 0,
       "rope_theta": 1e6, "rms_norm_eps": 1e-6,
       "router_aux_loss_coef": 1e-3, "embedding_init_std": 1.0,
       "arch": "sdar"}
SEQ = 32


@pytest.fixture(scope="module")
def _initialised():
    return small_model_test.initialised("sdar", CFG, SEQ)


@pytest.fixture
def small_model(_initialised):
    """(arch, main, forward, scope, cost, logits) as
    initialised, ONCE a file (tests/small_model_test.py)."""
    return small_model_test.as_initialised(*_initialised)


def _batch(rows=2):
    rng = np.random.RandomState(12)
    return {"src": rng.randint(3, 96, (rows, SEQ)).astype(np.int64),
            "label": np.zeros((rows, SEQ), np.int64),
            "mask": (rng.rand(rows, SEQ) > 0.2).astype(np.float32)}


def test_small_model_loss_and_logits_are_the_references(small_model):
    arch, main, forward, scope, cost, logits = small_model
    feed = _batch()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        params = arch.params_of_program(main, scope, CFG)
        assert int(params["salt"]) == 11 and int(params["step"]) == 0
        fetched = exe.run(forward, feed=feed, fetch_list=[cost, logits] + list(
            arch.router_choices(forward)))
    got_cost, got_logits, choices = fetched[0], fetched[1], fetched[2:]
    # the reference as ONE program each: eagerly it is a hundred small
    # compilations, more seconds than the model under test takes
    want = jax.jit(lambda p, *batch: arch.lm_loss(p, *batch, CFG))(
        params, feed["src"], feed["label"], feed["mask"])
    np.testing.assert_allclose(got_cost, want, rtol=2e-5)
    assert len(choices) == 2 and choices[0].shape == (2, 2 * SEQ, 4)
    logits_at = jax.jit(lambda p, tokens, chosen=None: arch.logits_at(
        p, tokens, 0, SEQ, CFG, chosen))
    for row in range(1):       # logits_at is batch row 0's draw
        ref = logits_at(params, jnp.asarray(feed["src"][row]))
        np.testing.assert_allclose(got_logits[row], ref, atol=2e-5)
        # handed the program's own choices the reference changes nothing
        handed = logits_at(params, jnp.asarray(feed["src"][row]),
                           np.stack([c[:1] for c in choices]))
        np.testing.assert_allclose(handed, ref, atol=1e-6)


def test_small_model_one_steps_gradients_are_the_references(small_model):
    """SGD at rate 1 turns a step's parameter change into its gradient:
    every parameter's against jax.grad of the reference's loss; the
    step counter and the experts' loads advance."""
    arch, main, _, scope, cost, _ = small_model
    feed = _batch()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        before = arch.params_of_program(main, scope, CFG)
        exe.run(main, feed=feed, fetch_list=[cost])
        after = arch.params_of_program(main, scope, CFG)
        counters = arch.program_counters(main, scope)
    assert counters["steps"] == [1] and int(after["step"]) == 1
    assert sum(counters["expert_rows"]) == 2 * 2 * (2 * SEQ) * 4
    floats = lambda p: {k: v for k, v in p.items()
                        if k not in ("salt", "step")}
    grads = jax.jit(jax.grad(lambda p: arch.lm_loss(
        {**p, "salt": before["salt"], "step": before["step"]},
        feed["src"], feed["label"], feed["mask"], CFG)))(floats(before))
    moved = jax.tree.map(lambda a, b: a - b, floats(before), floats(after))
    flat_g, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_m = jax.tree.leaves(moved)
    assert len(flat_g) == 3 + 2 * 12
    for (path, g), m in zip(flat_g, flat_m):
        scale = float(np.max(np.abs(g))) + 1e-8
        assert float(np.max(np.abs(g - m))) / scale < 2e-3, \
            jax.tree_util.keystr(path)
