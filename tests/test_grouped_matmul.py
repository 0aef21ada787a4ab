"""The held experts' grouped matmuls (ops/grouped_matmul.py, ISSUE 63)
in interpret mode on the CPU against `lax.ragged_dot` and
`lax.ragged_dot_general`: bfloat16 operands, float32 sums, the three
orientations, row tiles of 128 and 512.

What interpret mode cannot see (block shapes, VMEM) is compiled for a
described v5e by `tests/test_tpu_compile_experts.py`, and
`chip_smoke.py --phases experts` runs the kernels on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.ops import grouped_matmul as GM

CAP, K, N, HELD = 1024, 128, 256, 4
# rows to each of the four held experts of a chunk of 1,024 places
SIZES = {
    # the second expert sees no row
    "an_expert_with_no_row": [200, 0, 150, 100],
    # the first passes a tile of 128 by one row, the second ends inside
    # the next one, and the fourth passes a tile of 512 by one row
    "ends_inside_a_tile_and_passes_one_by_a_row": [129, 100, 27, 257],
    # expert 2 holds every place of the chunk
    "one_expert_holds_every_row": [0, 0, CAP, 0],
    # every tile whole
    "whole_tiles": [128, 256, 128, 0],
    "no_pair_at_all": [0, 0, 0, 0],
}
_BY_EXPERT = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _operands(seed, live):
    """(a [CAP, K], b [CAP, N], w [HELD, K, N]) bfloat16 with NaN in
    the places past the `live` that hold pairs."""
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s), jnp.bfloat16)
    a, b, w = mk(CAP, K), mk(CAP, N), mk(HELD, K, N)
    return a.at[live:].set(jnp.nan), b.at[live:].set(jnp.nan), w


@pytest.mark.parametrize("tm", [128, 512])
@pytest.mark.parametrize("which", ["rows", "rows_t", "by_expert"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_kernels_are_ragged_dot_on_the_rows_that_hold_pairs(
        case, which, tm):
    """The rows under the sum of the sizes (and every expert's block
    of `by_expert`) agree with XLA's op on the same bfloat16 operands;
    NaN planted past the pairs reaches nothing; an expert with no row
    gives exact zeros."""
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    live = sum(SIZES[case])
    a, b, w = _operands(len(case) + tm, live)
    rows, rows_t, by_expert = GM.grouped(sizes, tm, "interpret")
    # XLA's op reads the places past the pairs as zeros
    a0, b0 = a.at[live:].set(0), b.at[live:].set(0)
    if which == "by_expert":
        got = by_expert(a, b)
        want = lax.ragged_dot_general(a0, b0, sizes, _BY_EXPERT,
                                      preferred_element_type=jnp.float32)
        assert got.shape == (HELD, K, N) and got.dtype == jnp.float32
        for e, size in enumerate(SIZES[case]):
            if not size:
                assert float(jnp.max(jnp.abs(got[e]))) == 0.0
    else:
        dtype = jnp.float32 if which == "rows" else jnp.bfloat16
        got = rows(a, w, dtype) if which == "rows" else rows_t(
            a, jnp.swapaxes(w, 1, 2), dtype)
        want = lax.ragged_dot(a0, w, sizes, preferred_element_type=dtype)
        assert got.shape == (CAP, N) and got.dtype == dtype
        got, want = got[:live], want[:live]
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # float32 sums of the same products in another order; a bfloat16
    # result may round the other way
    tol = 1e-5 if got.dtype == want.dtype and which != "rows_t" else 1e-2
    np.testing.assert_allclose(
        got, want, rtol=tol,
        atol=tol * (np.abs(want).max(initial=0.0) + 1e-6))


@pytest.mark.parametrize("tm", [128, 256, 512])
@pytest.mark.parametrize("empty", [False, True], ids=["skipped", "visited"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_visits_cover_each_experts_rows_once_and_nothing_past_them(
        case, empty, tm):
    """`_visits`' tables against a walk on the host: every (expert,
    tile) that shares a row, in order, once; an expert with no row once
    where asked; no tile past the last pair."""
    sizes = SIZES[case]
    starts, expert, tile, steps = (np.asarray(v) for v in GM._visits(
        jnp.asarray(sizes, jnp.int32), CAP, tm, empty))
    ends = np.cumsum(sizes)
    assert starts.tolist() == [0] + ends.tolist()
    want = []
    for e, size in enumerate(sizes):
        lo, hi = ends[e] - size, ends[e]
        if size:
            want += [(e, t) for t in range(lo // tm, (hi - 1) // tm + 1)]
        elif empty:
            want.append((e, min(lo // tm, CAP // tm - 1)))
    assert list(zip(expert[:steps].tolist(), tile[:steps].tolist())) == want
    assert len(expert) == CAP // tm + HELD >= steps
    live = sum(sizes)
    assert all(t * tm < max(live, 1) for e, t in want if sizes[e])


@pytest.mark.parametrize("transposed", [False, True], ids=["rows", "rows_t"])
def test_a_contraction_cut_in_two_sums_to_the_same_rows(monkeypatch,
                                                        transposed):
    """A budget that no whole contraction fits (no cell's: a model
    wider than any here): the row kernels walk the contraction in
    blocks and keep the float32 sums between them."""
    k = 256
    monkeypatch.setattr(GM, "_BLOCK_BYTES", 400 * 1024)
    assert GM._tiles(128, k, N, 4) == (128, 128)
    sizes = jnp.asarray(SIZES["ends_inside_a_tile_and_passes_one_by_a_row"],
                        jnp.int32)
    live = int(sizes.sum())
    rng = np.random.RandomState(k)
    a = jnp.asarray(rng.randn(CAP, k), jnp.bfloat16).at[live:].set(jnp.nan)
    w = jnp.asarray(rng.randn(HELD, k, N), jnp.bfloat16)
    rows, rows_t, _ = GM.grouped(sizes, 128, "interpret")
    got = rows_t(a, jnp.swapaxes(w, 1, 2)) if transposed else rows(a, w)
    want = lax.ragged_dot(a.at[live:].set(0), w, sizes,
                          preferred_element_type=jnp.float32)
    np.testing.assert_allclose(got[:live], want[:live], rtol=1e-5, atol=1e-4)


def test_the_choice_is_xlas_on_a_cpu_and_wherever_a_width_is_no_lane_tile():
    like = jnp.zeros(())
    assert GM.choose(4096, (256, 128), like) == ("xla", 0)
    assert GM.choose(4096, (256, 128), like, "xla") == ("xla", 0)
    # the row tile is the probe's 256 wherever the chunk is whole tiles
    # of it (a routed cell's is whole tiles of 512)
    assert GM.choose(4096, (256, 128), like, "interpret") == (
        "interpret", 256)
    assert GM.choose(384, (256, 128), like, "pallas") == ("pallas", 128)
    # a forced kernel moves what it can take and nothing else
    assert GM.choose(4096, (256, 32), like, "interpret") == ("xla", 0)
    assert GM.choose(72, (256, 128), like, "pallas") == ("xla", 0)


@pytest.mark.parametrize("cell,tm,k,n,out_bytes,want", [
    ("xing4_up", 256, 3584, 2048, 4, (3584, 2048)),
    ("nemotron_down", 256, 2048, 2688, 4, (2048, 896)),
    ("lfm2_dxs", 256, 3584, 2048, 2, (3584, 2048)),
    ("joyai_up", 256, 2048, 1536, 4, (2048, 1536)),
    # twice Xing4.0's widths: the columns give way first, then the
    # contraction, and the blocks stay under the budget
    ("wider_than_any_cell", 256, 7168, 4096, 4, (7168, 1024)),
    ("a_contraction_no_block_holds", 256, 65536, 256, 4, (16384, 128))])
def test_a_row_kernels_blocks_hold_the_whole_contraction_where_it_fits(
        cell, tm, k, n, out_bytes, want):
    tk, tn = GM._tiles(tm, k, n, out_bytes)
    assert (tk, tn) == want and k % tk == 0 and n % tn == 0
    assert 2 * (tm * tk * 2 + tk * tn * 2 + tm * tn * out_bytes) \
        + tm * tn * 4 <= GM._BLOCK_BYTES
