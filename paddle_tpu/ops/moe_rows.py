"""The expert layer's way back: a chunk's rows added to their tokens.

The dropless expert layer (`parallel/moe.routed_experts`) sorts its
(row, expert) pairs by expert, runs the grouped matmuls on a chunk of
`cap` places, and adds what each place gives, times the pair's weight,
to the place's token. A chip of an expert-parallel group holds a
fraction of the experts, so about half of a chunk's places hold pairs;
XLA's `acc.at[rows].add(where(there, y * w, 0))` makes a pass over the
whole chunk for the product and the mask and then scatters every place,
at 97 GB/s on the bytes of the pairs (4.03 ms for 16,000 pairs of
[32768, 2048], 1.43 ms for 2,200 of [4096, 3584]: my chip run, PR 35).

  `moe_scatter_add_rows`  acc[rows[i]] += scale[i] * y[i] for i < count,
                          float32, in place order; blocks of the chunk
                          from `count` on are neither fetched nor
                          visited (0.76 and 0.20 ms for the same)

A row moves by ONE DMA. Mosaic slices a tiled array only at whole
tiles (8 rows of 32 bits), so a single row of [N, d] as XLA keeps it
cannot be a DMA's operand; the accumulator therefore lives, for the
length of the layer's loop over chunks, as a SLAB [N * t, 128] in which
a row is t whole 128-lane rows one after the other (t a multiple of 8:
the row padded to whole kilowords). Inside a grid step the block's rows
land in a VMEM slab [R * t, 128]; a sublane-strided view
(`pl.ds(c, R, stride=t)`) is lane tile c of all R rows as one [R, 128]
value, which is how the slab meets the chunk's ordinary [R, d] blocks.
`moe_leave_slab` ends the loop: one pass from the slab to [N, d] in the
dtype the layer returns.

The hazard: within one expert's run of the sorted order the rows ascend
(the sort is stable), so they are distinct; across a run's end a token
can come again. A block is cut wherever `rows` stops ascending
(`_next_cut`, a scan XLA makes of the [cap] indices) and read, add and
write are done one such stretch at a time, each stretch's writes landed
before the next stretch's reads are issued: exact for ANY `rows`, and
the same sum in the same order on every run.

The way IN (`x[rows]`) stays XLA's gather over the whole chunk: it runs
at the HBM's speed whatever the rows hold (0.21 ms for [32768, 2048]
bfloat16), where a row-DMA kernel of this kind took 0.36 ms on the
16,000 rows that hold pairs plus 0.20 ms to make its slab, and XLA's op
in a loop over blocks bounded by `count` 0.51 ms (my chip run, PR 35).

Dispatch (`_resolve_path`): the kernels on a TPU where a row is whole
lane tiles; XLA's form everywhere else (the CPU, odd widths). `force`
("pallas" / "interpret" / "xla") is for tests and probes.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu

_LANES = 128
# A grid step's VMEM slab, bytes: 1, 2 and 4 MB measured 0.90 / 0.92 /
# 0.99 ms at [32768, 2048] half full, 0.24 / 0.26 / 0.30 at [4096, 3584]
# (my chip run, PR 35).
_SLAB_BYTES = 1024 * 1024
_MIN_BLOCK = 64     # places: a block's parts are whole bfloat16 tiles


def _resolve_path(shape, like, force):
    """ "pallas" / "interpret" / "xla" for float32 sums into the rows of
    [N, d] = `shape`; `like`: an array whose device says whether this is
    a TPU."""
    n, d = shape
    usable = d % _LANES == 0 and n >= _MIN_BLOCK
    if force is None:
        return "pallas" if usable and _on_tpu(like) else "xla"
    if force != "xla" and not usable:
        raise ValueError("moe_rows: rows of %s are not whole lane tiles, or "
                         "fewer than a block of %d" % (shape, _MIN_BLOCK))
    return force


def _padded(d):
    """A row's float32 words, padded to whole kilowords."""
    return -(-d // 1024) * 1024


def _block_rows(dp, rows, most):
    """Rows a grid step holds: a power of two of at most _SLAB_BYTES
    and at most `most` that divides `rows`, in whole bfloat16 tiles."""
    r = _MIN_BLOCK
    while (2 * r * dp * 4 <= _SLAB_BYTES and rows % (2 * r) == 0
           and 2 * r <= most):
        r *= 2
    return r


def _wait_rows(m, block, t, src, dst, sem):
    """Wait for m (0 .. block) row copies from `src` to `dst` on `sem`:
    the semaphore counts bytes, so one wait of 2^b rows for each bit b
    set in m."""
    b = 1
    while b <= block:
        @pl.when((m & b) != 0)
        def _(b=b):
            pltpu.make_async_copy(src.at[pl.ds(0, b * t), :],
                                  dst.at[pl.ds(0, b * t), :], sem).wait()
        b *= 2


def _each_row(p, q, fn):
    """fn(r) for r in p .. q - 1, eight to a loop step where eight are
    left (unrolled by hand: the scalar core overlaps their address
    arithmetic, 1.24 -> 0.90 ms; sixteen gave 1% more; Mosaic's
    fori_loop takes no `unroll`)."""
    eights = (q - p) // 8

    def eight(i, carry):
        for j in range(8):
            fn(p + i * 8 + j)
        return carry

    def one(r, carry):
        fn(r)
        return carry

    lax.fori_loop(0, eights, eight, 0)
    lax.fori_loop(p + eights * 8, q, one, 0)


# Parts of a block, so that one's reads fly under the other's adds: 1, 2
# and 4 measured 0.87 / 0.76 / 0.83 ms at [32768, 2048] half full, 0.24 /
# 0.20 / 0.22 at [4096, 3584] (my chip run, PR 35).
_PARTS = 2


def _scatter_kernel(at_ref, cut_ref, count_ref, _, y_ref, *rest, block, t,
                    scaled):
    scale_ref = rest[0] if scaled else None
    # the accumulator is read and written through the result's ref, which
    # IS the operand (aliased)
    acc_hbm, buf, sems = rest[-3:]
    base = pl.program_id(0) * block
    live = jnp.minimum(count_ref[0] - base, block)
    tiles = y_ref.shape[1] // _LANES
    part = block // _PARTS
    place = lax.broadcasted_iota(jnp.int32, (part, 1), 0)

    def copy(r, sem, back):
        hbm = acc_hbm.at[pl.ds(pl.multiple_of(at_ref[base + r], 8), t), :]
        here = buf.at[pl.ds(pl.multiple_of(r * t, 8), t), :]
        return pltpu.make_async_copy(*((here, hbm) if back else (hbm, here)),
                                     sem)

    def stretch(p):
        """Places p .. q - 1 of the block, rows ascending, so distinct:
        read, add, write, a part of the block at a time, the next
        part's reads in flight while this one is added and sent back;
        all writes landed at the end. Returns q."""
        q = jnp.minimum(cut_ref[base + p] - base, live)
        # part g's share of the stretch: lo .. hi - 1, maybe none
        bounds = [(jnp.clip(p, g * part, (g + 1) * part),
                   jnp.clip(q, g * part, (g + 1) * part))
                  for g in range(_PARTS)]

        def read(g):
            _each_row(*bounds[g],
                      lambda r: copy(r, sems.at[g % 2], False).start())

        read(0)
        for g, (lo, hi) in enumerate(bounds):
            if g + 1 < _PARTS:
                read(g + 1)
            _wait_rows(hi - lo, part, t, acc_hbm, buf, sems.at[g % 2])

            @pl.when(hi > lo)
            def _(g=g, lo=lo, hi=hi):
                here = (place >= lo - g * part) & (place < hi - g * part)
                for c in range(tiles):
                    lanes = slice(c * _LANES, (c + 1) * _LANES)
                    y = y_ref[g * part:(g + 1) * part, lanes].astype(
                        jnp.float32)
                    if scaled:
                        y = y * scale_ref[g * part:(g + 1) * part, :]
                    at = pl.ds(g * part * t + c, part, stride=t)
                    buf[at, :] = buf[at, :] + jnp.where(here, y, 0.0)

            _each_row(lo, hi, lambda r: copy(r, sems.at[2], True).start())
        _wait_rows(q - p, block, t, buf, acc_hbm, sems.at[2])
        return q

    @pl.when(live > 0)
    def _():
        lax.while_loop(lambda p: p < live, stretch, 0)


def _next_cut(rows):
    """[cap] int32: for each place the first place after it whose row
    does not ascend (`cap` where none does): the end of its stretch of
    distinct rows."""
    cap = rows.shape[0]
    at = jnp.arange(1, cap + 1, dtype=jnp.int32)
    cut = jnp.where(jnp.concatenate(
        [rows[1:] <= rows[:-1], jnp.ones((1,), bool)]), at, cap)
    return lax.cummin(cut, reverse=True)


# jitted as the flash kernels are: a stack of layers traces and lowers
# each kernel once
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _scatter_add_slab(acc, y, rows, scale, count, n, d, interpret):
    extra = -rows.shape[0] % _MIN_BLOCK
    if extra:       # a chunk of no whole block (tiny layers): more tail
        rows = jnp.pad(rows, (0, extra))
        y = jnp.pad(y, ((0, extra), (0, 0)))
        scale = None if scale is None else jnp.pad(scale, (0, extra))
    cap = rows.shape[0]
    dp = _padded(d)
    block, t = _block_rows(dp, cap, n), dp // _LANES
    # a step past the pairs keeps pointing at the last block that holds
    # some: nothing is fetched for it
    at = lambda i, rows, cut, count: (jnp.minimum(i, jnp.maximum(
        (count[0] + block - 1) // block - 1, 0)), 0)
    # a row's place in the slab, made here once for both of its DMAs
    places = jnp.clip(rows, 0, n - 1) * t
    operands, specs = [acc, y], [pl.BlockSpec(memory_space=pl.ANY),
                                 pl.BlockSpec((block, d), at)]
    if scale is not None:
        operands.append(scale.astype(jnp.float32).reshape(cap, 1))
        specs.append(pl.BlockSpec((block, 1), at))
    return pl.pallas_call(
        functools.partial(_scatter_kernel, block=block, t=t,
                          scaled=scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(cap // block,),
            in_specs=specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            # two semaphores for the parts' reads in turn, one for writes
            scratch_shapes=[pltpu.VMEM((block * t, _LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA((3,))]),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        # operand 3: the accumulator, after the three prefetched scalars
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="moe_scatter_add_rows",
    )(places, _next_cut(rows), jnp.reshape(count, (1,)).astype(jnp.int32),
      *operands)


def _leave_kernel(slab_ref, out_ref, *, block, t, dtype):
    for c in range(out_ref.shape[1] // _LANES):
        out_ref[:, c * _LANES:(c + 1) * _LANES] = slab_ref[
            pl.ds(c, block, stride=t), :].astype(dtype).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _leave_slab(slab, n, d, dtype, out_dtype, interpret):
    dp = _padded(d)
    block, t = _block_rows(dp, n, n), dp // _LANES
    if n % block:       # rows of no whole block: not worth a kernel
        return slab.reshape(n, dp)[:, :d].astype(dtype).astype(out_dtype)
    return pl.pallas_call(
        functools.partial(_leave_kernel, block=block, t=t, dtype=dtype),
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block * t, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="moe_leave_slab",
    )(slab)


# -- what the expert layer calls: an accumulator carried through its loop
# -- over chunks, in whichever form the path wants

def zeros(shape, path):
    """A float32 accumulator for [N, d] = `shape`."""
    n, d = shape
    if path == "xla":
        return jnp.zeros(shape, jnp.float32)
    return jnp.zeros((n * _padded(d) // _LANES, _LANES), jnp.float32)


def scatter_add(acc, shape, y, rows, scale, count, path):
    """acc with scale[i] * y[i] (float32) added to row rows[i], i <
    count; y's places from `count` on reach no sum."""
    if path == "xla":
        y = y.astype(jnp.float32)
        if scale is not None:
            y = y * scale[:, None]
        there = jnp.arange(rows.shape[0], dtype=jnp.int32) < count
        return acc.at[rows].add(jnp.where(there[:, None], y, 0.0))
    n, d = shape
    return _scatter_add_slab(acc, y, rows, scale, count, n, d,
                             path == "interpret")


def result(acc, shape, dtype, path, out_dtype=None):
    """The accumulator as [N, d], rounded to `dtype` and handed on as
    `out_dtype` (`dtype` if None): the layer computes in its weights'
    dtype and answers in its input's, one pass for both casts."""
    out_dtype = jnp.dtype(out_dtype or dtype)
    if path == "xla":
        return acc.astype(dtype).astype(out_dtype)
    return _leave_slab(acc, *shape, jnp.dtype(dtype).name, out_dtype.name,
                       path == "interpret")


def scatter_add_rows(acc, y, rows, scale, count, force=None):
    """acc [N, d] float32 with acc[rows[i]] += scale[i] * y[i] for i <
    count (rows [cap] int32, y [cap, d], scale [cap] or None): float32
    additions in place order. Where the kernel runs, acc enters and
    leaves its slab here; the layer keeps it there across its chunks."""
    n, d = acc.shape
    path = _resolve_path(acc.shape, acc, force)
    if path != "xla":
        acc = jnp.pad(acc, ((0, 0), (0, _padded(d) - d))).reshape(
            -1, _LANES)
    acc = scatter_add(acc, (n, d), y, rows, scale, count, path)
    return result(acc, (n, d), jnp.float32, path)
