"""The causal depthwise convolution in front of a scan or a delta rule,
with its SiLU, as a Pallas TPU kernel pair under ONE ``jax.custom_vjp``
(ISSUE 65): what the Program op ``ssm_conv`` lowers to on a TPU
(``ops/selective_scan.py`` ``causal_conv_silu`` dispatches here).

The operator, for x ``[B, T, C]``, K taps w ``[K, C]`` and a bias
``[C]`` or none, each channel by itself, zeros before the sequence,
float32 inside and x's dtype out::

    pre_t = bias + sum_i w[i] * x_(t - K + 1 + i)         y_t = silu(pre_t)

The ``jax.numpy`` form (``short_conv.causal_taps``: K shifted slices of
a float32 copy padded along T, and autodiff's transpose of them) took
six to seven times the op's bytes on the chip in the Mamba-2 cells
(``PERF.md`` section 6, PR 65). The kernels:

* ``ssm_conv_fwd``: grid ``(B, C / Cb, T / tile)``, T innermost. A
  grid step holds ``[tile, Cb]`` of x as the projection left it (bf16
  under AMP; nothing padded or turned in HBM) and walks it a lane tile
  (128 channels) and `_GROUP` rows at a time, first rows first: the
  group widened to float32, the K - 1 rows before it from the group
  before (a loop carry: 8 rows; across grid steps a VMEM scratch, zeros
  at the sequence's start), the taps added in the taps' order from the
  bias as `causal_taps` adds them, SiLU, one write of y. x is read once
  and no float32 ``[T, C]`` value reaches HBM.
* ``ssm_conv_bwd``: the residuals are x, w and the bias, nothing else:
  ``pre`` is made again from x in VMEM. The grid walks T's tiles LAST
  to first and a step its groups last to first, so that the K - 1 rows
  of ``dpre = dy silu'(pre)`` AFTER a group are the group's that was
  walked before it (a loop carry, a scratch across steps, zeros at the
  sequence's end); the K - 1 rows of x BEFORE a tile are one 16-row
  block of the same array (a second BlockSpec on x: 16 rows are a bf16
  tile). ``dx_t = sum_i w[i] dpre_(t + K - 1 - i)`` in x's dtype;
  ``dw[i] = sum_t dpre_t x_(t - K + 1 + i)`` and ``dbias = sum_t
  dpre_t`` as float32 partial sums by sublane, ``[B, K + 1, 8, C]``,
  a block that stays in VMEM over T's tiles and is written once a
  (batch, channel block); XLA adds the 8 B partials.

A row shift is a sublane shift: ``ext[8 - (K - 1) + i:][:rows]`` of
``[8 rows before; the group]``.

Tiles: ``Cb`` is the most lane tiles up to `_CHANNELS` that divide C
where C is whole lane tiles; a width that is not (Olmo-Hybrid's 1,440
and 2,880) walks blocks of `_CHANNELS` lanes over a grid of ``cdiv(C,
Cb)``, the last block partly outside the array (what it reads there is
never written back: a channel's result depends on that channel alone),
or, up to `_CHANNELS` wide, one block of all of C. T is padded with
zero rows to whole tiles inside the wrapper (a zero row after the
sequence changes no row before it, and a zero ``dy`` row adds nothing
to any gradient). `kernel_tiles` says None where the kernels do not
apply (more than 9 taps), and the caller keeps the ``jax.numpy`` form.

Each lowering counts itself in ``ptpu_ssm_conv_lowerings_total{path,
direction, taps, channels}``.

Sizes (my chip runs, PR 65; ``PERF.md`` section 6 has the readings
they were chosen from): `_ROWS`, `_CHANNELS`, `_GROUP` below.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..monitor import metrics as _metrics
from .flash_attention import _largest_divisor

# `chip_smoke.py --phases conv` on a TPU v5e with the sizes below (my
# chip runs, PR 65): x [1, 8192, C] bf16, 4 taps, device ms a call; the
# backward is forward + backward in one executable less the forward;
# the bytes at 819 GB/s; y is bit for bit the jax.numpy path's at every
# width, dx within a bfloat16 rounding (1.1e-3 to 2.4e-3 of the largest
# value), dw and dbias within 3.1e-7.
#
#   C (bias)     forward: kernel  jax.numpy  bytes   backward: kernel  jax.numpy  bytes
#   4096 (yes)            0.255    1.158     0.164             0.482    3.183     0.246
#    128 (yes)            0.010    0.011     0.005             0.017    0.018     0.008
#   1024 (no)             0.066    0.068     0.041             0.123    0.299     0.061
#   5120 (no)             0.315    1.459     0.205             0.589    4.042     0.307
#   1440 (no)             0.167    0.102     0.058             0.290    0.578     0.086
#   2880 (no)             0.418    0.202     0.115             0.640    2.698     0.173
#
# The vector unit sets the pace (7.3 and 13.8 cycles a float32 vreg). At
# 4,096 channels an inner step of 128 rows read 0.255 / 0.482 where 64
# read 0.289 / 0.521, 32 0.379 / 0.618 and 256 0.254 / 0.495; tiles of
# 512 to 2,048 rows and blocks of 256 to 1,024 lanes within 2% of each
# other (128 lanes: 0.335 forward). A width that is not whole lane tiles
# runs at half the pace whatever the block: ONE block of all of C read
# 0.160 / 0.282 and 0.411 / 0.621 there, no better, and its 12 and 23
# lane tiles, each a loop traced by itself, cost olmohybrid_train_T8k 8 s
# of set-up a run (31 -> 41 s).
_LANES = 128
_HALO = 16          # rows of the block before a tile: a bf16 tile's
_ROWS = 1024        # rows a grid step holds
_CHANNELS = 512     # channels a grid step holds (whole lane tiles)
_GROUP = 128        # rows an inner step walks, a lane tile at a time
_VMEM_BYTES = 64 * 1024 * 1024
_F32 = jnp.float32

_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_ssm_conv_lowerings_total",
    "causal depthwise convolution + SiLU (the Program op ssm_conv) "
    "dispatches at trace time (one a lowering of a direction, none a "
    "step): the path taken (pallas: the kernel pair; interpret: the "
    "same on the CPU; taps: jax.numpy's shifted slices), the direction, "
    "the taps and the channels",
    ("path", "direction", "taps", "channels"))


def count(path, direction, w):
    _LOWERINGS.inc(path=path, direction=direction, taps=str(w.shape[0]),
                   channels=str(w.shape[1]))


def kernel_tiles(t, c, k, rows=None):
    """(tile, Cb, group) of the kernels' walk over ``[T, C]`` under K
    taps, or None where they do not apply. `rows`: the most rows a grid
    step holds (None: `_ROWS`)."""
    if not 1 <= k <= 9:
        return None
    up = lambda n, m: -(-n // m) * m
    tile = min(up(rows or _ROWS, _HALO), up(t, _HALO))
    group = max(g for g in (_GROUP, 64, 32, 16) if tile % g == 0)
    if c % _LANES:
        return tile, min(c, _CHANNELS), group
    return tile, _LANES * _largest_divisor(c // _LANES,
                                           _CHANNELS // _LANES), group


def _lane_tiles(cb):
    return [slice(at, min(at + _LANES, cb)) for at in range(0, cb, _LANES)]


def _taps(ext, k, rows):
    """The K shifted views of ``[8 rows before; rows]``, the oldest
    first: tap i reads x_(t - K + 1 + i)."""
    return [ext[8 - (k - 1) + i:8 - (k - 1) + i + rows] for i in range(k)]


def _pre(taps, w, bias):
    out = bias
    for x_i, w_i in zip(taps, w):
        term = w_i * x_i
        out = term if out is None else out + term
    return out


def _fwd_kernel(*refs, k, group, biased):
    x_ref, w_ref = refs[:2]
    y_ref, tail_scr = refs[-2:]
    tile, cb = x_ref.shape[1:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        tail_scr[...] = jnp.zeros(tail_scr.shape, _F32)

    for lanes in _lane_tiles(cb):
        w = [w_ref[i:i + 1, lanes].astype(_F32) for i in range(k)]
        bias = refs[2][:, lanes].astype(_F32) if biased else None

        def walk(g, tail):
            rows = pl.ds(pl.multiple_of(g * group, group), group)
            x = x_ref[0, rows, lanes].astype(_F32)
            pre = _pre(_taps(jnp.concatenate([tail, x], 0), k, group), w,
                       bias)
            y_ref[0, rows, lanes] = (pre * jax.nn.sigmoid(pre)).astype(
                y_ref.dtype)
            return x[group - 8:]

        tail_scr[:, lanes] = lax.fori_loop(0, tile // group, walk,
                                           tail_scr[:, lanes])


def _bwd_kernel(*refs, k, group, biased):
    x_ref, halo_ref, dy_ref, w_ref = refs[:4]
    dx_ref, sums_ref, head_scr = refs[-3:]
    tile, cb = x_ref.shape[1:]
    at, tiles = pl.program_id(2), pl.num_programs(2)
    groups = tile // group

    @pl.when(at == 0)           # the LAST tile: the walk is reversed
    def _():
        head_scr[...] = jnp.zeros(head_scr.shape, _F32)
        sums_ref[...] = jnp.zeros(sums_ref.shape, _F32)

    by_sublane = lambda v: v.reshape(group // 8, 8, v.shape[-1]).sum(0)
    for lanes in _lane_tiles(cb):
        width = lanes.stop - lanes.start
        w = [w_ref[i:i + 1, lanes].astype(_F32) for i in range(k)]
        bias = refs[4][:, lanes].astype(_F32) if biased else None
        # the 8 rows before the tile: zeros before the sequence
        halo = jnp.where(at == tiles - 1, 0.0,
                         halo_ref[0, _HALO - 8:, lanes].astype(_F32))

        def walk(j, carry):
            head, sums = carry
            g = groups - 1 - j
            start = pl.multiple_of(g * group, group)
            rows = pl.ds(start, group)
            x = x_ref[0, rows, lanes].astype(_F32)
            before = x_ref[0, pl.ds(pl.multiple_of(
                jnp.maximum(start - _HALO, 0), _HALO), _HALO),
                           lanes].astype(_F32)[_HALO - 8:]
            taps = _taps(jnp.concatenate(
                [jnp.where(g == 0, halo, before), x], 0), k, group)
            pre = _pre(taps, w, bias)
            sig = jax.nn.sigmoid(pre)
            dpre = dy_ref[0, rows, lanes].astype(_F32) * (
                sig * (1.0 + pre * (1.0 - sig)))
            after = jnp.concatenate([dpre, head], 0)
            dx = None
            for i in range(k):
                term = w[i] * after[k - 1 - i:k - 1 - i + group]
                dx = term if dx is None else dx + term
            dx_ref[0, rows, lanes] = dx.astype(dx_ref.dtype)
            sums = tuple(s + by_sublane(dpre * x_i)
                         for s, x_i in zip(sums, taps)) + (
                (sums[k] + by_sublane(dpre),) if biased else ())
            return dpre[:8], sums

        zero = jnp.zeros((8, width), _F32)
        head, sums = lax.fori_loop(
            0, groups, walk, (head_scr[:, lanes], (zero,) * (k + biased)))
        head_scr[:, lanes] = head
        for i, s in enumerate(sums):
            sums_ref[0, i, :, lanes] += s


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES)


def _padded(x, tp):
    return x if x.shape[1] == tp else jnp.pad(
        x, [(0, 0), (0, tp - x.shape[1]), (0, 0)])


def _walk(x, w, bias, rows):
    """What both directions share: (K, the tile's rows, an inner step's
    rows, T padded to whole tiles, the grid, w and the bias as the
    kernels read them, their BlockSpecs, an ``[tile, Cb]`` block's
    shape)."""
    (bsz, t, c), k = x.shape, w.shape[0]
    tile, cb, group = kernel_tiles(t, c, k, rows)
    tp = -(-t // tile) * tile
    small = [w] + ([] if bias is None else [bias[None, :]])
    specs = [pl.BlockSpec((v.shape[0], cb), lambda b, c, t: (0, c))
             for v in small]
    return (k, tile, group, tp, (bsz, -(-c // cb), tp // tile), small, specs,
            (1, tile, cb))


# jitted, as the scans' wrappers are: a stack of layers traces and
# lowers each kernel once a shape, and the kernels keep their own names
# in the compiled program whatever transformation traced the call
@functools.partial(jax.jit, static_argnums=(3, 4))
def _fwd_pallas(x, w, bias, rows, interpret):
    k, tile, group, tp, grid, small, specs, shape = _walk(x, w, bias, rows)
    block = pl.BlockSpec(shape, lambda b, c, t: (b, t, c))
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, group=group,
                          biased=bias is not None),
        grid=grid, in_specs=[block] + specs, out_specs=block,
        out_shape=jax.ShapeDtypeStruct((x.shape[0], tp, x.shape[2]),
                                       x.dtype),
        scratch_shapes=[pltpu.VMEM((8, shape[2]), _F32)],
        compiler_params=_params(), interpret=interpret,
        name="ssm_conv_fwd")(_padded(x, tp), *small)
    return y[:, :x.shape[1]]


@functools.partial(jax.jit, static_argnums=(4, 5))
def _bwd_pallas(x, w, bias, dy, rows, interpret):
    """The cotangents of (x, w, bias): dx in x's dtype, dw and dbias
    float32."""
    k, tile, group, tp, grid, small, specs, shape = _walk(x, w, bias, rows)
    (bsz, t, c), cb, last = x.shape, shape[2], grid[2] - 1
    n_sums = k + len(small) - 1         # dw's K rows and, biased, dbias's
    block = pl.BlockSpec(shape, lambda b, c, t: (b, last - t, c))
    halo = pl.BlockSpec(
        (1, _HALO, cb), lambda b, c, t: (
            b, jnp.maximum((last - t) * (tile // _HALO) - 1, 0), c))
    xp = _padded(x, tp)
    dx, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, k=k, group=group,
                          biased=bias is not None),
        grid=grid, in_specs=[block, halo, block] + specs,
        out_specs=[block, pl.BlockSpec((1, n_sums, 8, cb),
                                       lambda b, c, t: (b, 0, 0, c))],
        out_shape=[jax.ShapeDtypeStruct((bsz, tp, c), x.dtype),
                   jax.ShapeDtypeStruct((bsz, n_sums, 8, c), _F32)],
        scratch_shapes=[pltpu.VMEM((8, cb), _F32)],
        compiler_params=_params(), interpret=interpret,
        name="ssm_conv_bwd")(xp, xp, _padded(dy, tp), *small)
    sums = sums.sum((0, 2))
    return dx[:, :t], sums[:k], (None if bias is None else sums[k])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(x, w, bias, rows, interpret):
    return _fwd_pallas(x, w, bias, rows, interpret)


def _conv_fwd(x, w, bias, rows, interpret):
    return _fwd_pallas(x, w, bias, rows, interpret), (x, w, bias)


def _conv_bwd(rows, interpret, res, dy):
    x, w, bias = res
    count("interpret" if interpret else "pallas", "bwd", w)
    dx, dw, db = _bwd_pallas(x, w, bias, dy, rows, interpret)
    return dx, dw.astype(w.dtype), (None if bias is None
                                    else db.astype(bias.dtype))


_conv.defvjp(_conv_fwd, _conv_bwd)


def conv_silu(x, w, bias=None, rows=None, interpret=False):
    """``silu(bias + the causal taps of x)`` through the kernel pair: x
    [B, T, C], w [K, C], bias [C] or None; differentiable in all three.
    `rows`: the most rows a grid step holds (None: `_ROWS`);
    `interpret`: the kernels on the CPU. The caller has asked
    `kernel_tiles` first."""
    count("interpret" if interpret else "pallas", "fwd", w)
    return _conv(x, w, bias, rows, interpret)


# pallas imports at the end, as ``flash_attention.py`` has them: a
# CPU-only environment that never takes the kernels still imports this
from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402
