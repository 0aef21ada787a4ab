"""The embedding's gradient: a segment sum over the ids in sorted order.

The gradient of `take(w, ids)` adds T rows dy[i] into a float32 table
[V, d] at ids[i]. XLA's scatter on this chip walks the rows one after
another (it sorts the ids itself first) at 0.14-0.4 us a distinct row of
up to 2048 floats and 1.1-1.5 us a wider one: 16.8 ms of
`smallthinker_train_T16k`'s step and 8.8 of `olmohybrid_train_T8k`'s for
0.7 and 0.4 ms of bytes (ledger, PRs 46 and 54; ISSUE 58).

  `embedding_grad_rows`  table[v] = the sum of dy[i] over ids[i] == v,
                         float32, in token order; zeros where no id fell

Sorted by id (stable: token order inside a run of equal ids), every
table row is WRITTEN ONCE and never read back, and the rows of one block
of the table are one stretch of the sorted places. XLA makes the order
(a sort of T keys), gathers dy by it (at the HBM's speed) and finds each
table block's stretch (`searchsorted`); the kernel's grid then walks
ITEMS, one for each pair (block of table rows, chunk of sorted
places: `_blocks`) that share places, in sorted order: at most
blocks + chunks of them, both operands' blocks following the items
through scalar-prefetched index maps, so the pipeline fetches a chunk
once and writes a block once, whole (no zeros pass, no accumulator
aliased in, nothing to leave). An item adds its places' rows of dy to
their rows of the block one at a time, in place order: the same sum in
the same order on every run.

Dispatch (`_resolve_path`): the kernel on a TPU for a float32 table
whose rows are whole lane tiles, at EVERY such width (at d 1024 and 2048
XLA's scatter-add takes 0.94 and 2.64 ms for the path's 0.41 and 2.02:
my chip run, PR 58); XLA's scatter-add everywhere else (the CPU, odd
widths, other dtypes, more ids than SMEM holds). `force` ("pallas" /
"interpret" / "xla") is for tests and probes, and for the lowering
(`ops/tensor_ops.py` `_lookup_table`), which asks for XLA's form for a
sparse, a distributed or a tied table and under a mesh.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..monitor import metrics as _metrics
from .flash_attention import _on_tpu
from .moe_rows import _LANES, _each_row

# A chunk of sorted places and a block of table rows, two of each in
# flight: 64 rows and 128 places up to d 4096. At (16384, 37984, 2560) /
# (8192, 12544, 3840) / (32768, 8192, 2048) the kernel took 1.027 / 0.600
# / 0.683 ms with 64 and 64, 0.927 / 0.532 / 0.666 with 64 and 128, 0.960
# / 0.566 / 0.608 with 128 and 128 (my chip run, PR 58).
_CHUNK_BYTES = 2 * 1024 * 1024
# 32-bit words the sorted ids and the items' four lists may take of the
# chip's SMEM (1 MiB: 131,072 ids compile for a v5e, 262,144 do not)
_SMEM_WORDS = 3 * 65536


def _blocks(d):
    """(table rows to a block, sorted places to a chunk) for rows of d
    floats: whole sublane tiles, a chunk of at most _CHUNK_BYTES."""
    places = 128
    while places > 8 and places * d * 4 > _CHUNK_BYTES:
        places //= 2
    return min(64, places), places


_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_embedding_grad_lowerings_total",
    "embedding lookups lowered, by what their gradient will be (one a "
    "lowering of the lookup, none a step): the path (pallas: the "
    "kernel embedding_grad_rows over the sorted ids, a TPU's for float32 "
    "rows of whole lane tiles; interpret: the same on the CPU, tests "
    "only; xla: XLA's scatter-add, every other device, width and dtype), "
    "the ids a step looks up, and the table's rows and their width",
    ("path", "rows", "vocab", "width"))


def _resolve_path(ids, shape, dtype, like, force):
    """ "pallas" / "interpret" / "xla" for the gradient of `ids` lookups
    in a table [V, d] = `shape` of `dtype`; `like`: an array whose
    device says whether this is a TPU."""
    rows, places = _blocks(shape[1])
    words = ids + places + 4 * (-(-shape[0] // rows) + -(-ids // places))
    usable = (shape[1] % _LANES == 0 and jnp.dtype(dtype) == jnp.float32
              and words <= _SMEM_WORDS)
    if force is None:
        return "pallas" if usable and _on_tpu(like) else "xla"
    if force != "xla" and not usable:
        raise ValueError(
            "embedding_grad: %d lookups in a table %s of %s: not float32 "
            "rows of whole lane tiles, or over %d words of SMEM" % (
                ids, shape, dtype, _SMEM_WORDS))
    return force


def _kernel(block_ref, chunk_ref, lo_ref, hi_ref, ids_ref, dy_ref, out_ref):
    rows, places = out_ref.shape[0], dy_ref.shape[0]
    k = pl.program_id(0)
    block = block_ref[k]

    # an item's first of its block: the rows no id reaches stay zero
    @pl.when((k == 0) | (block != block_ref[jnp.maximum(k - 1, 0)]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    row0, place0 = block * rows, chunk_ref[k] * places

    def add(p):
        row = pl.ds(ids_ref[p] - row0, 1)
        out_ref[row, :] = out_ref[row, :] + dy_ref[pl.ds(p - place0, 1), :]

    _each_row(lo_ref[k], hi_ref[k], add)


def _items(ids, rows, places, blocks, chunks):
    """The grid's items over ids sorted ascending (padded to `chunks`
    whole chunks of `places` with ids past every one of the `blocks`
    blocks of `rows`): for each of blocks + chunks items its table
    block, its chunk, and its places lo .. hi - 1. A block with no place
    keeps one empty item (it is written as zeros); the items past the
    last pair are empty and stay on the last pair's block and chunk, so
    nothing moves for them."""
    i32 = jnp.int32
    # `compare_all`: one fused pass each; the default is a `while` of
    # gathers, a dozen dispatches for a few hundred numbers
    bounds = jnp.searchsorted(
        ids, jnp.arange(blocks + 1, dtype=i32) * rows,
        method="compare_all").astype(i32)
    lo, hi = bounds[:-1], bounds[1:]
    first = jnp.minimum(lo // places, chunks - 1)
    count = jnp.maximum((hi - 1) // places, first) - first + 1
    ends = jnp.cumsum(count)
    k = jnp.arange(blocks + chunks, dtype=i32)
    block = jnp.minimum(jnp.searchsorted(ends, k, side="right",
                                         method="compare_all"),
                        blocks - 1).astype(i32)
    nth = k - (ends - count)[block]
    live = nth < count[block]
    chunk = first[block] + jnp.minimum(nth, count[block] - 1)
    at = jnp.maximum(lo[block], chunk * places)
    to = jnp.minimum(hi[block], (chunk + 1) * places)
    return block, chunk, at, jnp.where(live, to, at)


# jitted as the flash kernels are: a Program with two lookups in one
# table traces and lowers the kernel once
@functools.partial(jax.jit, static_argnums=(2, 3))
def _segment_sum(ids, dy, vocab, interpret):
    (t,), d = ids.shape, dy.shape[1]
    rows, places = _blocks(d)
    blocks, chunks = -(-vocab // rows), -(-t // places)
    # places past T sort behind every id and reach no block
    ids = jnp.pad(jnp.clip(ids, 0, vocab - 1), (0, chunks * places - t),
                  constant_values=blocks * rows)
    ids, order = jax.lax.sort(
        (ids, jnp.arange(ids.shape[0], dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    block, chunk, lo, hi = _items(ids, rows, places, blocks, chunks)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(blocks + chunks,),
            in_specs=[pl.BlockSpec(
                (places, d), lambda k, block, chunk, *_: (chunk[k], 0))],
            out_specs=pl.BlockSpec(
                (rows, d), lambda k, block, *_: (block[k], 0))),
        out_shape=jax.ShapeDtypeStruct((vocab, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="embedding_grad_rows",
    )(block, chunk, lo, hi, ids,
      jnp.take(dy, order, axis=0, mode="clip").astype(jnp.float32))


def embedding_grad(ids, dy, vocab, path):
    """The float32 table [vocab, d] of dy's rows ([T, d]) summed at ids
    ([T] int32, clipped to the table)."""
    if path == "xla":
        return jnp.zeros((vocab, dy.shape[1]), jnp.float32).at[
            jnp.clip(ids, 0, vocab - 1)].add(dy.astype(jnp.float32))
    return _segment_sum(ids, dy, vocab, path == "interpret")


# the table's rows ride as a static argument: a residual would reach the
# backward as an array wherever the two are traced apart
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _take_rows(w, ids, vocab, path):
    return jnp.take(w, ids, axis=0)


def _take_rows_fwd(w, ids, vocab, path):
    return jnp.take(w, ids, axis=0), ids


def _take_rows_bwd(vocab, path, ids, dy):
    dw = embedding_grad(ids.reshape(-1), dy.reshape(-1, dy.shape[-1]), vocab,
                        path)
    return dw.astype(dy.dtype), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def take_rows(w, ids, force=None):
    """w[ids] for ids already inside the table, as `jnp.take` gives it;
    its gradient by `embedding_grad_rows` where `_resolve_path` says so,
    and `jnp.take`'s own (XLA's scatter-add) everywhere else."""
    path = _resolve_path(ids.size, w.shape, w.dtype, w, force)
    _LOWERINGS.inc(path=path, rows=str(ids.size), vocab=str(w.shape[0]),
                   width=str(w.shape[1]))
    if path == "xla":
        return jnp.take(w, ids, axis=0)
    return _take_rows(w, ids, w.shape[0], path)
