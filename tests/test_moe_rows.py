"""The expert layer's way back (ops/moe_rows.py, ISSUE 35) in interpret
mode on the CPU against its jax.numpy form, alone and through
`parallel/moe.routed_experts`.

`moe_scatter_add_rows` adds each place's row of a chunk, times its
weight, to its token in float32, visiting only the places that hold
pairs; `moe_leave_slab` brings the accumulator back from the kernel's
own layout. What interpret mode cannot see (block shapes, VMEM, the
DMA's alignment) `tests/test_tpu_compile_experts.py` compiles for a
described v5e, and `chip_smoke.py`'s `rows` and `experts` phases run on
the chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe_rows as MR
from paddle_tpu.parallel import moe

N, CAP = 64, 128      # two blocks of 64 places to a chunk
WIDTHS = [(256, jnp.float32), (256, jnp.bfloat16), (2048, jnp.bfloat16),
          (2048, jnp.float32), (3584, jnp.bfloat16), (3584, jnp.float32)]
COUNTS = [0, 37, 64, CAP]
_id = lambda w: "%d_%s" % (w[0], jnp.dtype(w[1]).name)


def _runs(rng, lengths, n=N):
    """Sorted runs as the layer's order has them: ascending (distinct)
    tokens within an expert, tokens coming again across experts."""
    return np.concatenate([np.sort(rng.choice(n, m, replace=False))
                           for m in lengths]).astype(np.int32)


@pytest.mark.parametrize("width", WIDTHS, ids=_id)
def test_the_accumulator_leaves_its_slab_in_the_layers_dtype(width):
    """Rows of 256, 2048 and 3584 (padded to 4096 in the slab) come
    back as they went in, cast once."""
    d, dtype = width
    acc = jnp.asarray(np.random.RandomState(d).randn(N, d), jnp.float32)
    slab = jnp.pad(acc, ((0, 0), (0, MR._padded(d) - d))).reshape(-1, 128)
    assert slab.shape == MR.zeros((N, d), "interpret").shape
    got = MR.result(slab, (N, d), dtype, "interpret")
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(acc.astype(dtype), np.float32))


@pytest.mark.parametrize("scaled", [True, False], ids=["scale", "no_scale"])
@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("width", [(256, jnp.float32), (2048, jnp.float32),
                                   (3584, jnp.bfloat16)], ids=_id)
def test_scatter_add_rows_sums_in_float32_and_never_reads_the_tail(
        width, count, scaled):
    """y's dtype is the width's (the forward adds float32 rows, dx
    bfloat16 ones). The runs cut the first block at 50 and the second at
    90, and most tokens come in all three: the hazard. NaN fills y and
    the weights from `count` on."""
    d, dtype = width
    rng = np.random.RandomState(d + count)
    # drawn, filled and summed on the host: nothing is compiled but the
    # kernel and XLA's form
    acc = rng.randn(N, d).astype(np.float32)
    y = rng.randn(CAP, d).astype(jnp.dtype(dtype))
    scale = rng.rand(CAP).astype(np.float32)
    y[count:], scale[count:] = np.nan, np.nan
    rows = _runs(rng, [50, 40, 38])
    assert len(set(rows[:50]) & set(rows[50:64])) > 0   # inside one block
    want = acc.copy()
    for i in range(count):
        want[rows[i]] += np.float32(
            scale[i] if scaled else 1.0) * y[i].astype(np.float32)
    acc, y, scale = jnp.asarray(acc), jnp.asarray(y), jnp.asarray(scale)
    run = lambda: MR.scatter_add_rows(acc, y, jnp.asarray(rows),
                                      scale if scaled else None, count,
                                      force="interpret")
    got = np.asarray(run())
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got, run())           # the same twice
    np.testing.assert_allclose(
        MR.scatter_add_rows(acc, y, jnp.asarray(rows),
                            scale if scaled else None, count, force="xla"),
        want, rtol=1e-6, atol=1e-6)


def test_a_token_two_held_experts_chose_across_a_block_cut_is_exact():
    """Places 62 .. 65 hold tokens 7, 9 | 7, 9: the experts' boundary IS
    the blocks' boundary (64), and token 7 comes three times more, early
    in the first block and in the second. Every sum is exact: small integers."""
    d = 256
    rows = np.arange(CAP, dtype=np.int32) % N
    rows[60:68] = [3, 5, 7, 9, 7, 9, 11, 13]
    rows[20:24] = [20, 21, 7, 22]                       # 7 once more, early
    y = jnp.asarray(1.0 + np.arange(CAP)[:, None] * np.ones((1, d)),
                    jnp.float32)
    got = MR.scatter_add_rows(jnp.zeros((N, d), jnp.float32), y,
                              jnp.asarray(rows), None, CAP,
                              force="interpret")
    want = np.zeros((N, d), np.float32)
    np.add.at(want, rows, np.asarray(y))
    np.testing.assert_array_equal(got, want)
    assert want[7, 0] == 8 + 23 + 63 + 65 + 72          # five places


@pytest.mark.parametrize("chunk", [0, 1, 2])
def test_a_later_chunk_moves_its_own_pairs(chunk):
    """`_chunk`'s rows and count for chunk c of a sorted order longer
    than a chunk: the kernel takes them as it takes the first's."""
    d, k, held = 256, 2, 4
    rng = np.random.RandomState(chunk)
    order = jnp.asarray(rng.permutation(3 * CAP), jnp.int32)
    ends = jnp.asarray([90, 170, 250, 300], jnp.int32)
    pairs, rows, count, sizes = moe._chunk(chunk, CAP, order, ends, k)
    want_count = (128, 128, 44)[chunk]
    assert int(count) == want_count == int(sizes.sum())
    y = jnp.asarray(rng.randn(CAP, d), jnp.float32)
    acc = jnp.zeros((3 * CAP // k, d), jnp.float32)
    np.testing.assert_allclose(
        MR.scatter_add_rows(acc, y, rows, None, count, force="interpret"),
        MR.scatter_add_rows(acc, y, rows, None, count, force="xla"),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape", [(16384, 2048), (4096, 3584), (4096, 64),
                                   (4096, 200), (32, 2048)], ids=str)
def test_the_path_is_chosen_from_device_and_shape(shape, monkeypatch):
    acc = jnp.zeros((8, 8), jnp.float32)
    assert MR._resolve_path(shape, acc, None) == "xla"      # this is a CPU
    assert MR._resolve_path(shape, acc, "xla") == "xla"
    # on a TPU: the kernel where a row is whole lane tiles and the rows
    # fill a block
    monkeypatch.setattr(MR, "_on_tpu", lambda like: True)
    whole = shape[1] % 128 == 0 and shape[0] >= 64
    assert MR._resolve_path(shape, acc, None) == (
        "pallas" if whole else "xla")
    if whole:
        assert MR._resolve_path(shape, acc, "interpret") == "interpret"
    else:
        with pytest.raises(ValueError, match="whole lane tiles"):
            MR._resolve_path(shape, acc, "interpret")


def test_a_chunk_of_no_whole_block_is_padded_with_more_tail():
    """72 places (a tiny layer's chunk: a multiple of 8, of no block)."""
    d, cap = 256, 72
    rng = np.random.RandomState(9)
    acc = jnp.asarray(rng.randn(N, d), jnp.float32)
    y = jnp.asarray(rng.randn(cap, d), jnp.float32)
    rows = jnp.asarray(_runs(rng, [40, 32]))
    scale = jnp.asarray(rng.rand(cap), jnp.float32)
    for count in (50, cap):
        np.testing.assert_allclose(
            MR.scatter_add_rows(acc, y, rows, scale, count,
                                force="interpret"),
            MR.scatter_add_rows(acc, y, rows, scale, count, force="xla"),
            rtol=1e-6, atol=1e-6)


# -- through the expert layer ---------------------------------------------------

E, HELD, F = 16, 4, 32
ROUTERS = {
    "softmax_top8": dict(top_k=8, score="softmax"),
    "softmax_top8_relu": dict(top_k=8, score="softmax", activation="relu"),
    "sigmoid_top4_bias": dict(top_k=4, score="sigmoid", scaling=2.0,
                              bias=jnp.linspace(-0.2, 0.2, E)),
}


def _layer(d, seed, n=N):
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    return (mk(n, d), mk(d, E) * 0.3, mk(HELD, d, F) * d ** -0.5,
            mk(HELD, d, F) * d ** -0.5, mk(HELD, F, d) * F ** -0.5)


def _on_the_first(x, wr, k):
    """One huge feature decides the choice: experts 0 .. k - 1 of the
    16, whatever the row."""
    return x.at[:, 0].set(8.0), (wr * 0.01).at[0, :k].set(5.0)


def _kernel_against_xla(args, k, dtype, how):
    """((out, counts), the five gradients) by `force="interpret"`, the
    same by `force="xla"` held to it: float32 experts agree to rounding;
    under bfloat16 dx parts by a bfloat16 ulp or two."""
    def loss(force, x, wr, wg, wu, wd):
        out, aux, counts, _ = moe.routed_experts(
            x, wr, wg.astype(dtype), wu.astype(dtype), wd.astype(dtype), E,
            0, k, True, force=force, **how)
        return (out.astype(jnp.float32) ** 2).sum() + aux, (out, counts)

    # each path's loss and gradients as ONE program: dispatched eagerly
    # the layer's loop and its transpose are compiled an op at a time
    got, want = (jax.jit(jax.value_and_grad(
        functools.partial(loss, force), (0, 1, 2, 3, 4), has_aux=True))(
            *args) for force in ("interpret", "xla"))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    close = lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=tol * float(jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-6))
    close(got[0][1][0], want[0][1][0])
    for a, b in zip(got[1], want[1]):
        close(a, b)
    return got[0][1], got[1]


@pytest.mark.parametrize("all_held", [False, True],
                         ids=["routers_own", "every_row_on_held_experts"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_routed_experts_by_the_kernel_matches_xlas_scatter_add(
        router, dtype, all_held):
    """Output and all five gradients, `force="interpret"` against
    `force="xla"`, a SiLU and a ReLU gate. With every row on held
    experts the one chunk is full and nothing is dropped (two chunks:
    the next test). float32 experts agree to rounding; under bfloat16
    dx parts by a bfloat16 ulp or two: the kernel reads the grouped
    matmul's bfloat16 dxs as written, XLA's fused scatter-add keeps
    excess precision."""
    how = dict(ROUTERS[router])
    k = how.pop("top_k")
    x, wr, wg, wu, wd = _layer(256, len(router))
    if all_held:
        # experts 0 .. k - 1 of the 16, so a held share of 8 holds them
        x, wr = _on_the_first(x, wr, k)
        how.pop("bias", None)
        wg, wu, wd = (jnp.concatenate([w, w * 0.5]) for w in (wg, wu, wd))
    (_, counts), _ = _kernel_against_xla((x, wr, wg, wu, wd), k, dtype, how)
    if all_held:
        assert int(counts[:8].sum()) == N * k


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_second_chunk_adds_to_the_firsts_gradients(dtype):
    """256 rows, every one on the 4 held experts of 16: 1,024 pairs in
    chunks of 512, so both loops run twice: the backward's carry is
    chunk 0's own gradients (ISSUE 47) with chunk 1's added, and every
    expert's rows lie across the two."""
    x, wr, wg, wu, wd = _layer(256, 47, n=4 * N)
    x, wr = _on_the_first(x, wr, 4)
    (_, counts), grads = _kernel_against_xla(
        (x, wr, wg, wu, wd), 4, dtype, dict(score="sigmoid", scaling=2.0))
    assert counts.tolist() == [4 * N] * 4 + [0] * 12
    # (the router's scores are saturated, its gradient an exact zero)
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in grads[:1] + grads[2:])


def test_the_lowering_counter_says_what_moved_the_rows():
    x, wr, wg, wu, wd = _layer(256, 3)
    labels = dict(path="ragged_dot", experts=str(E), experts_held=str(HELD),
                  top_k="2", score="softmax", shared_expert="false",
                  activation="silu", router_input="own")
    was = {rows: moe._LOWERINGS.value(rows=rows, **labels)
           for rows in ("interpret", "xla", "pallas")}
    moe.routed_experts(x, wr, wg, wu, wd, E, 0, 2, force="interpret")
    moe.routed_experts(x, wr, wg, wu, wd, E, 0, 2)      # a CPU: XLA's
    now = {rows: moe._LOWERINGS.value(rows=rows, **labels) for rows in was}
    assert now == {"interpret": was["interpret"] + 1, "xla": was["xla"] + 1,
                   "pallas": was["pallas"]}
