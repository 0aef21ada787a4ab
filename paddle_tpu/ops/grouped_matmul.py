"""The held experts' grouped matmuls as Pallas kernels.

The dropless expert layer (`parallel/moe.routed_experts`) sorts a
chunk's (row, expert) pairs by expert and multiplies each expert's rows
by that expert's weights: `sizes` [Eh] rows to each held expert in turn,
about half of the chunk's `C` places filled, the places past the pairs
holding other experts' rows. Three kernels make a pass's seven products:

  `grouped_matmul_rows`       rows [C, K] x weights [Eh, K, N] -> [C, N]
  `grouped_matmul_rows_t`     rows [C, K] x weights [Eh, N, K] -> [C, N]:
                              the weights read as they lie, contracted on
                              their LAST axis, so that the backward's
                              products against `w_down` and `w_gu`
                              transposed copy neither
  `grouped_matmul_by_expert`  a [C, M], b [C, N] -> [Eh, M, N] float32:
                              each expert's own rows contracted, exact
                              zeros for an expert with none

The scheme is that of `jax.experimental.pallas.ops.tpu.megablox` (gmm /
tgmm): the chunk is cut into row tiles of `tm`; a grid step is one
(expert, row tile) VISIT, the visits listed by expert in three small
tables the scalar core reads (`_visits`), and a tile that straddles two
experts is visited once for each, masked to the visiting expert's rows.
The grid's extent over visits is the number of visits that hold rows, a
traced value: no tile past the sum of the sizes is fetched or computed.
What differs from megablox: a visit whose tile lies wholly inside its
expert's rows takes no mask; the row kernels hold the WHOLE contraction
in a block where it fits (`_tiles`), so that an expert's block of
weights stays in VMEM across its row tiles and is read once from HBM for
every column tile; `by_expert` sums into its result's block in place;
and the row tile is 256 rows, which the probe by rows an expert found
best from 256 to 4,096 rows an expert (`_ROW_TILE`).

Operands in their own dtype (bfloat16 under AMP), sums float32, results
in the dtype the caller asks for. Rows past the sum of the sizes are
NOT written: what a caller finds there is whatever the buffer held
(`lax.ragged_dot` writes zeros there; the expert layer reads none of
them).

Dispatch (`choose`): the kernels on a TPU where every width is
whole lane tiles and the chunk whole row tiles; `lax.ragged_dot`
everywhere else (the CPU, odd widths). `force` ("pallas" / "interpret" /
"xla") is for tests and probes, as in `ops/moe_rows.py`.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu

_LANES = 128
_MIN_TILE = 128     # rows: the smallest row tile, one pass of the MXU
# What a kernel's blocks may hold of the chip's 128 MiB of VMEM, both
# buffers of each counted: the largest block set of the seven routed
# cells (`_tiles`: Xing4.0's up projection, a contraction of 3,584 whole
# at 2,048 columns) is 39 MB, where Mosaic's own limit of 16 MiB would
# cut the contraction.
_VMEM_BYTES = 96 * 1024 * 1024
_BLOCK_BYTES = 40 * 1024 * 1024


# The row tile. The probe by rows an expert (`chip_smoke.py --phases
# grouped`; my chip run, PR 63, this file as it stands): device ms of a
# pass's six products (up, down, dh, dxs, dW_up, dW_down) at each routed
# cell's chunk, filled as uniform routing fills it, XLA's `ragged-dot`
# kernels against these at row tiles of 128 / 256 / 512:
#
#   rows an expert (cell)        XLA     128     256     512
#     256  (xing4, d 3,584)     2.946   1.922   1.940   2.264
#     256  (joyai, 16 held)     2.843   1.739   1.787   2.056
#     384  (nemotron, ungated)  5.781   2.356   2.394   2.710
#   1,024  (sdar, 16 held)      5.877   3.735   3.745   4.081
#   1,024  (trinity)            3.551   2.425   2.437   2.651
#   1,536  (smallthinker)      10.376   6.574   6.383   6.762
#   4,096  (lfm2)              19.920  13.189  12.895  13.140
#
# The kernels are ahead at every shape and in each of the three
# orientations alone, so `lax.ragged_dot` is off the TPU path; and ONE
# tile, 256 rows, is the best or within 2.7% of it at every count of
# rows (128 leads by 0.3 to 2.7% up to 1,024 rows an expert, 256 by 2
# to 3% from 1,536: 0.05 ms of a pass either way, not worth a rule on
# the rows an expert sees). Inside a tile of 512 the MXU runs at 98% of
# its peak, of 256 at 83% and of 128 at 68% (Nemotron's `rows`, time
# over rows visited), but an expert's rows end inside a tile and a tile
# that straddles two experts is computed for each: at 384 rows an
# expert a tile of 512 is visited 1.75 times an expert.
_ROW_TILE = 256


def choose(rows, widths, like, force=None):
    """(path, row tile) for the grouped matmuls of a chunk of `rows`
    places whose operands are `widths` wide: ("pallas" | "interpret",
    tm) for the kernels, ("xla", 0) for `lax.ragged_dot`. `like`: an
    array whose device says whether this is a TPU. `force` ("pallas" /
    "interpret" / "xla", the expert layer's, for tests and probes)
    moves what the kernels can take; a chunk of no whole row tile or a
    width of no whole lane tiles stays `lax.ragged_dot`'s whatever it
    says."""
    usable = rows % _MIN_TILE == 0 and all(w % _LANES == 0 for w in widths)
    path = force or ("pallas" if _on_tpu(like) else "xla")
    if path == "xla" or not usable:
        return "xla", 0
    return path, _MIN_TILE if rows % _ROW_TILE else _ROW_TILE


def _visits(sizes, rows, tm, empty):
    """The (expert, row tile) visits of sorted rows, `sizes` to each
    expert in turn, in the order a grid walks them: (starts [Eh + 1]:
    expert g's rows are starts[g] .. starts[g + 1] - 1; expert [S] and
    tile [S] of each visit; how many visits there are), int32, S the
    static bound ``rows // tm + Eh``. An expert with no row is visited
    once where `empty` (its result has to be zeroed), else not at all;
    the tables' places past the last visit repeat it and are never
    walked."""
    held = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    n = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                  1 if empty else 0)
    upto = jnp.cumsum(n)
    step = jnp.arange(rows // tm + held, dtype=jnp.int32)
    # (the experts whose visits end at or before this one, counted:
    # one small fusion where a search would be a loop)
    expert = jnp.minimum(jnp.sum(step[:, None] >= upto[None, :], axis=1,
                                 dtype=jnp.int32), held - 1)
    tile = jnp.clip(first[expert] + step - (upto - n)[expert], 0,
                    rows // tm - 1)
    return (jnp.concatenate([starts, ends[-1:]]), expert, tile,
            upto[-1])


def _own_rows(starts_ref, expert_ref, tile_ref, s, tm):
    """Visit s: (which of its tile's rows are the visiting expert's, a
    [tm, 1] mask; whether all of them are; whether any is)."""
    g = expert_ref[s]
    lo, hi, base = starts_ref[g], starts_ref[g + 1], tile_ref[s] * tm
    rows = base + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return ((rows >= lo) & (rows < hi), (lo <= base) & (hi >= base + tm),
            hi > lo)


def _rows_kernel(starts_ref, expert_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                 *acc, tm, tiles_k, transposed):
    s, k_i = pl.program_id(1), pl.program_id(2)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))
    part = lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                           preferred_element_type=jnp.float32)
    if tiles_k > 1:
        acc_ref, = acc

        @pl.when(k_i == 0)
        def _():
            acc_ref[...] = part

        @pl.when(k_i > 0)
        def _():
            acc_ref[...] += part

    def store():
        total = acc[0][...] if tiles_k > 1 else part
        own, whole, _ = _own_rows(starts_ref, expert_ref, tile_ref, s, tm)

        @pl.when(whole)
        def _():
            out_ref[...] = total.astype(out_ref.dtype)

        # a tile that straddles experts keeps what the visits before
        # this one wrote of it: the block stays in VMEM between them
        @pl.when(jnp.logical_not(whole))
        def _():
            out_ref[...] = jnp.where(
                own, total,
                out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    if tiles_k > 1:
        pl.when(k_i == tiles_k - 1)(store)
    else:
        store()


def _by_expert_kernel(starts_ref, expert_ref, tile_ref, a_ref, b_ref, out_ref,
                      *, tm):
    s = pl.program_id(2)

    @pl.when((s == 0)
             | (expert_ref[jnp.maximum(s - 1, 0)] != expert_ref[s]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    own, whole, some = _own_rows(starts_ref, expert_ref, tile_ref, s, tm)
    over_rows = (((0,), (0,)), ((), ()))

    @pl.when(whole)
    def _():
        out_ref[...] += lax.dot_general(a_ref[...], b_ref[...], over_rows,
                                        preferred_element_type=jnp.float32)

    # the sum runs over rows: another expert's row must be an exact zero
    # on BOTH sides (0 x NaN is NaN)
    @pl.when(jnp.logical_not(whole) & some)
    def _():
        a = jnp.where(own, a_ref[...], jnp.zeros_like(a_ref))
        b = jnp.where(own, b_ref[...], jnp.zeros_like(b_ref))
        out_ref[...] += lax.dot_general(a, b, over_rows,
                                        preferred_element_type=jnp.float32)


def _divisor(n, most):
    """The largest whole number of lane tiles that divides n and is at
    most `most` (at least one lane tile)."""
    best = _LANES
    for t in range(_LANES, min(n, most) + 1, _LANES):
        if n % t == 0:
            best = t
    return best


def _tiles(tm, k, n, out_bytes):
    """(tk, tn) of a row kernel's blocks, lhs [tm, tk] x weights
    [tk, tn]: the whole contraction and the most columns, up to 2,048,
    that `_BLOCK_BYTES` hold with both buffers of each block counted;
    the contraction is cut only where one lane tile of columns would
    not fit beside it. (The whole contraction is what keeps an expert's
    weights in VMEM across its row tiles: cut to 512 the same products
    took 1.7 times as long; 2,048 columns for 1,024 took 1 to 5% less,
    512 took 4 to 10% more: Nemotron's and Xing4.0's shapes, my chip
    run, PR 63.)"""
    def held(tk, tn):
        return 2 * (tm * tk * 2 + tk * tn * 2 + tm * tn * out_bytes) \
            + tm * tn * 4
    tn = _divisor(n, 2048)
    while tn > _LANES and held(k, tn) > _BLOCK_BYTES:
        tn = _divisor(n, tn - _LANES)
    tk = k
    while tk > _LANES and held(tk, tn) > _BLOCK_BYTES:
        tk = _divisor(k, tk - _LANES)
    return tk, tn


def _out_tiles(m, n):
    """(tmo, tno) of `by_expert`'s result block [tmo, tno] float32,
    summed into in place over an expert's row tiles a [tm, tmo] and b
    [tm, tno]: up to 1,024 by 2,048 (3 to 6% under 1,024 by 1,024's
    time and 17% under 512 by 1,024's: the operands are read again for
    every block of the other's width; the same run)."""
    return _divisor(m, 1024), _divisor(n, 2048)


# jitted as the row kernels of moe_rows are: a stack of layers traces
# and lowers each kernel once
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _rows(lhs, rhs, visits, transposed, dtype, tm, interpret):
    starts, expert, tile, steps = visits
    rows, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    tk, tn = _tiles(tm, k, n, jnp.dtype(dtype).itemsize)
    tiles_k = k // tk
    if transposed:
        rhs_spec = pl.BlockSpec(
            (None, tn, tk), lambda j, s, c, st, ex, ti: (ex[s], j, c))
    else:
        rhs_spec = pl.BlockSpec(
            (None, tk, tn), lambda j, s, c, st, ex, ti: (ex[s], c, j))
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, tiles_k=tiles_k,
                          transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, steps, tiles_k),
            in_specs=[pl.BlockSpec(
                (tm, tk), lambda j, s, c, st, ex, ti: (ti[s], c)), rhs_spec],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, s, c, st, ex, ti: (ti[s], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if tiles_k > 1 else []),
        out_shape=jax.ShapeDtypeStruct((rows, n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="grouped_matmul_rows_t" if transposed else "grouped_matmul_rows",
    )(starts, expert, tile, lhs, rhs)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _by_expert(a, b, visits, held, tm, interpret):
    starts, expert, tile, steps = visits
    m, n = a.shape[1], b.shape[1]
    tmo, tno = _out_tiles(m, n)
    return pl.pallas_call(
        functools.partial(_by_expert_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(m // tmo, n // tno, steps),
            in_specs=[
                pl.BlockSpec((tm, tmo),
                             lambda i, j, s, st, ex, ti: (ti[s], i)),
                pl.BlockSpec((tm, tno),
                             lambda i, j, s, st, ex, ti: (ti[s], j))],
            out_specs=pl.BlockSpec(
                (None, tmo, tno), lambda i, j, s, st, ex, ti: (ex[s], i, j))),
        out_shape=jax.ShapeDtypeStruct((held, m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret, name="grouped_matmul_by_expert",
    )(starts, expert, tile, a, b)


def grouped(sizes, tm, path):
    """(rows, rows_t, by_expert) on sorted rows, `sizes` [Eh] to each
    expert in turn, in row tiles of `tm`, `path` "pallas" or
    "interpret":

      rows(lhs [C, K], w [Eh, K, N], dtype=float32)    -> [C, N]
      rows_t(lhs [C, K], w [Eh, N, K], dtype=float32)  -> [C, N]
      by_expert(a [C, M], b [C, N])                    -> [Eh, M, N] float32

    The tables of visits are made once for the three."""
    interpret = path == "interpret"
    held = sizes.shape[0]
    tables = {}

    def visits(rows, empty):
        if (rows, empty) not in tables:
            tables[rows, empty] = _visits(sizes, rows, tm, empty)
        return tables[rows, empty]

    def rows(lhs, w, dtype=jnp.float32, transposed=False):
        return _rows(lhs, w, visits(lhs.shape[0], False), transposed,
                     jnp.dtype(dtype), tm, interpret)

    def by_expert(a, b):
        return _by_expert(a, b, visits(a.shape[0], True), held, tm,
                          interpret)

    return rows, functools.partial(rows, transposed=True), by_expert
