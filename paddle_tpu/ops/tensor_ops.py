"""Tensor manipulation + creation ops.

Reference parity: operators/{fill_constant,fill_zeros_like,assign,cast,concat,
split,reshape,transpose,expand,gather,scatter,one_hot,uniform_random,
gaussian_random,lookup_table,pad,increment,multiplex,label_smooth,
assign_value,shape,slice,is_empty}_op.cc.

Random ops consume a fresh PRNG key from the trace context (functional
randomness — the TPU-native replacement for the reference's cuRAND states).
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..core.program import convert_dtype, runtime_dtype
from .common import I64
from ..core.registry import register
from . import embedding_grad


def _np_dtype(d):
    return jnp.dtype(runtime_dtype(d))


@register("fill_constant", stateful_rng=False)
def _fill_constant(ctx, op):
    shape = op.attr("shape", [1])
    dtype = _np_dtype(op.attr("dtype", "float32"))
    value = op.attr("value", 0.0)
    ctx.set_out(op, "Out", jnp.full(tuple(shape), value, dtype=dtype))


@register("fill_constant_batch_size_like")
def _fill_cbsl(ctx, op):
    ref = ctx.in1(op, "Input")
    shape = list(op.attr("shape"))
    in_idx = op.attr("input_dim_idx", 0)
    out_idx = op.attr("output_dim_idx", 0)
    shape[out_idx] = ref.shape[in_idx]
    dtype = _np_dtype(op.attr("dtype", "float32"))
    ctx.set_out(op, "Out",
                jnp.full(tuple(shape), op.attr("value", 0.0), dtype=dtype))


@register("fill_zeros_like")
def _fill_zeros_like(ctx, op):
    ctx.set_out(op, "Out", jnp.zeros_like(ctx.in1(op, "X")))


@register("fill_any_like")
def _fill_any_like(ctx, op):
    x = ctx.in1(op, "X")
    ctx.set_out(op, "Out", jnp.full_like(x, op.attr("value", 0.0)))


@register("assign")
def _assign(ctx, op):
    ctx.set_out(op, "Out", ctx.in1(op, "X"))


@register("assign_value")
def _assign_value(ctx, op):
    shape = op.attr("shape")
    dtype = _np_dtype(op.attr("dtype", "float32"))
    values = op.attr("values")
    if isinstance(values, np.ndarray):
        arr = values.astype(dtype)
    else:
        arr = np.array(values, dtype=dtype)
    ctx.set_out(op, "Out", jnp.asarray(arr.reshape(shape)))


@register("cast")
def _cast(ctx, op):
    x = ctx.in1(op, "X")
    ctx.set_out(op, "Out", x.astype(_np_dtype(op.attr("out_dtype"))))


@register("concat")
def _concat(ctx, op):
    xs = ctx.in_list(op, "X")
    ctx.set_out(op, "Out", jnp.concatenate(xs, axis=op.attr("axis", 0)))


@register("split")
def _split(ctx, op):
    x = ctx.in1(op, "X")
    axis = op.attr("axis", 0)
    sections = op.attr("sections")
    num = op.attr("num", 0)
    if sections:
        idx = np.cumsum(sections)[:-1].tolist()
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    for name, val in zip(op.output("Out"), outs):
        ctx.env[name] = val


@register("reshape")
@register("reshape2")
def _reshape(ctx, op):
    x = ctx.in1(op, "X")
    shape = list(op.attr("shape"))
    # reference: 0 means copy input dim at that position
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    ctx.set_out(op, "Out", x.reshape(shape))


@register("squeeze")
def _squeeze(ctx, op):
    x = ctx.in1(op, "X")
    axes = op.attr("axes", [])
    if axes:
        ctx.set_out(op, "Out", jnp.squeeze(x, axis=tuple(axes)))
    else:
        ctx.set_out(op, "Out", jnp.squeeze(x))


@register("unsqueeze")
def _unsqueeze(ctx, op):
    x = ctx.in1(op, "X")
    for a in sorted(op.attr("axes", [])):
        x = jnp.expand_dims(x, a)
    ctx.set_out(op, "Out", x)


@register("transpose")
@register("transpose2")
def _transpose(ctx, op):
    x = ctx.in1(op, "X")
    ctx.set_out(op, "Out", jnp.transpose(x, axes=op.attr("axis")))


@register("expand")
def _expand(ctx, op):
    x = ctx.in1(op, "X")
    times = op.attr("expand_times")
    ctx.set_out(op, "Out", jnp.tile(x, tuple(times)))


@register("stack")
def _stack(ctx, op):
    xs = ctx.in_list(op, "X")
    ctx.set_out(op, "Y", jnp.stack(xs, axis=op.attr("axis", 0)))


@register("unstack")
def _unstack(ctx, op):
    x = ctx.in1(op, "X")
    axis = op.attr("axis", 0)
    outs = [jnp.squeeze(s, axis) for s in jnp.split(x, x.shape[axis], axis)]
    for name, val in zip(op.output("Y"), outs):
        ctx.env[name] = val


@register("gather")
def _gather(ctx, op):
    x = ctx.in1(op, "X")
    idx = ctx.in1(op, "Index")
    ctx.set_out(op, "Out", jnp.take(x, idx.astype(jnp.int32), axis=0))


@register("scatter")
def _scatter(ctx, op):
    x = ctx.in1(op, "X")
    idx = ctx.in1(op, "Ids").astype(jnp.int32)
    upd = ctx.in1(op, "Updates")
    if op.attr("overwrite", True):
        out = x.at[idx].set(upd)
    else:
        out = x.at[idx].add(upd)
    ctx.set_out(op, "Out", out)


@register("one_hot")
def _one_hot(ctx, op):
    x = ctx.in1(op, "X")
    depth = op.attr("depth")
    x = x.reshape(x.shape[:-1]) if x.shape and x.shape[-1] == 1 else x
    ctx.set_out(op, "Out", jax.nn.one_hot(x.astype(jnp.int32), depth))


# Random ops are stateful_rng: each draw advances the trace-order PRNG
# stream, so the transform tier must pin them in place (removing or
# deduplicating one would shift every later op's stream position).
@register("uniform_random", stateful_rng=True)
@register("uniform_random_batch_size_like", stateful_rng=True)
def _uniform_random(ctx, op):
    shape = list(op.attr("shape"))
    ref = ctx.maybe_get(op.input("Input")[0]) if op.input("Input") else None
    if ref is not None:
        shape[op.attr("output_dim_idx", 0)] = ref.shape[op.attr("input_dim_idx", 0)]
    dtype = _np_dtype(op.attr("dtype", "float32"))
    lo = op.attr("min", -1.0)
    hi = op.attr("max", 1.0)
    out = jax.random.uniform(ctx.rng(), tuple(shape), dtype=jnp.float32,
                             minval=lo, maxval=hi).astype(dtype)
    ctx.set_out(op, "Out", out)


@register("gaussian_random", stateful_rng=True)
@register("gaussian_random_batch_size_like", stateful_rng=True)
def _gaussian_random(ctx, op):
    shape = list(op.attr("shape"))
    ref = ctx.maybe_get(op.input("Input")[0]) if op.input("Input") else None
    if ref is not None:
        shape[op.attr("output_dim_idx", 0)] = ref.shape[op.attr("input_dim_idx", 0)]
    dtype = _np_dtype(op.attr("dtype", "float32"))
    mean = op.attr("mean", 0.0)
    std = op.attr("std", 1.0)
    out = mean + std * jax.random.normal(ctx.rng(), tuple(shape),
                                         dtype=jnp.float32)
    ctx.set_out(op, "Out", out.astype(dtype))


@register("truncated_gaussian_random", stateful_rng=True)
def _truncated_gaussian_random(ctx, op):
    shape = tuple(op.attr("shape"))
    dtype = _np_dtype(op.attr("dtype", "float32"))
    mean = op.attr("mean", 0.0)
    std = op.attr("std", 1.0)
    out = mean + std * jax.random.truncated_normal(
        ctx.rng(), -2.0, 2.0, shape, dtype=jnp.float32)
    ctx.set_out(op, "Out", out.astype(dtype))


def _tied(op, table):
    """Whether an op that is no lookup reads `table` as an operand: a
    tied head's `mul`. XLA's scatter-add then accumulates in place into
    that op's weight gradient; a kernel's separate result is summed
    inside the head's weight-gradient fusion instead, which then waits
    for the step's last gradient with its operands held:
    `lfm2_train_T32k`'s compiled step 1.56 GB more temporaries (my chip
    run, PR 58)."""
    return any(table in other.input_names
               and table not in other.input("Param")    # the optimizer's
               for block in op.block.program.blocks for other in block.ops
               if other.type != "lookup_table")


@register("lookup_table")
def _lookup_table(ctx, op):
    """Embedding lookup (operators/lookup_table_op.cc). ids may have a
    trailing 1 dim (reference convention). padding_idx rows read as zero.
    The table's gradient is ops/embedding_grad.py's where its dispatch
    says so (a TPU, float32 rows of whole lane tiles); a sparse, a
    distributed or a tied table keeps XLA's scatter-add, and so does a
    step across a mesh (GSPMD cannot partition a Mosaic kernel)."""
    w = ctx.in1(op, "W")
    ids = ctx.in1(op, "Ids").astype(jnp.int32)
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = ids.reshape(ids.shape[:-1])
    padding_idx = op.attr("padding_idx", -1)
    plain = (op.attr("is_sparse", False) or op.attr("is_distributed", False)
             or (ctx.mesh is not None and ctx.mesh.size > 1)
             or _tied(op, op.input("W")[0]))
    out = embedding_grad.take_rows(w, jnp.clip(ids, 0, w.shape[0] - 1),
                                   force="xla" if plain else None)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    ctx.set_out(op, "Out", out)


@register("pad")
def _pad(ctx, op):
    x = ctx.in1(op, "X")
    paddings = op.attr("paddings")  # flat [before0, after0, before1, ...]
    pads = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    ctx.set_out(op, "Out", jnp.pad(x, pads,
                                   constant_values=op.attr("pad_value", 0.0)))


@register("pad_constant_like")
def _pad_constant_like(ctx, op):
    x = ctx.in1(op, "X")    # big
    y = ctx.in1(op, "Y")    # small
    pads = [(0, xs - ys) for xs, ys in zip(x.shape, y.shape)]
    ctx.set_out(op, "Out", jnp.pad(y, pads,
                                   constant_values=op.attr("pad_value", 0.0)))


@register("crop")
def _crop(ctx, op):
    x = ctx.in1(op, "X")
    offsets = op.attr("offsets")
    shape = op.attr("shape")
    slices = tuple(slice(o, o + s) for o, s in zip(offsets, shape))
    ctx.set_out(op, "Out", x[slices])


@register("slice")
def _slice(ctx, op):
    x = ctx.in1(op, "Input")
    axes = op.attr("axes")
    starts = op.attr("starts")
    ends = op.attr("ends")
    slices = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        slices[a] = slice(s, e)
    ctx.set_out(op, "Out", x[tuple(slices)])


@register("shape")
def _shape(ctx, op):
    x = ctx.in1(op, "Input")
    ctx.set_out(op, "Out", jnp.asarray(x.shape, dtype=I64()))


@register("increment")
def _increment(ctx, op):
    x = ctx.in1(op, "X")
    # keep x's dtype: int counters must stay int (a python-float step would
    # silently promote and break while-loop carry types)
    ctx.set_out(op, "Out", x + jnp.asarray(op.attr("step", 1.0), x.dtype))


@register("step_sum")
def _step_sum(ctx, op):
    """SumOut = Sum [1] float32 + the scalar X in a train run, Sum in a
    for_test clone's: a counter of the program's own, so no gradient."""
    total = ctx.in1(op, "Sum")
    if not (op.attr("is_test", False) or ctx.is_test):
        total = total + jax.lax.stop_gradient(
            ctx.in1(op, "X")).astype(total.dtype).reshape(total.shape)
    ctx.set_out(op, "SumOut", total)


@register("multiplex")
def _multiplex(ctx, op):
    ids = ctx.in1(op, "Ids").astype(jnp.int32).reshape(-1)
    xs = jnp.stack(ctx.in_list(op, "X"), axis=0)   # [K, B, ...]
    ctx.set_out(op, "Out", xs[ids, jnp.arange(xs.shape[1])])


@register("label_smooth")
def _label_smooth(ctx, op):
    x = ctx.in1(op, "X")
    eps = op.attr("epsilon", 0.0)
    dist = ctx.in1(op, "PriorDist")
    k = x.shape[-1]
    if dist is not None:
        out = (1 - eps) * x + eps * dist
    else:
        out = (1 - eps) * x + eps / k
    ctx.set_out(op, "Out", out)


@register("is_empty")
def _is_empty(ctx, op):
    x = ctx.in1(op, "X")
    ctx.set_out(op, "Out", jnp.asarray(x.size == 0))


@register("range")
def _range(ctx, op):
    start = ctx.in1(op, "Start")
    end = ctx.in1(op, "End")
    step = ctx.in1(op, "Step")
    try:
        ctx.set_out(op, "Out",
                    jnp.arange(float(start), float(end), float(step)))
    except jax.errors.TracerArrayConversionError:
        raise NotImplementedError(
            "range op requires static Start/End/Step (constants), got "
            "traced values — XLA needs static output shapes")


@register("linspace")
def _linspace(ctx, op):
    start = op.attr("start")
    stop = op.attr("stop")
    num = op.attr("num")
    ctx.set_out(op, "Out", jnp.linspace(start, stop, num))


@register("sequence_mask")
def _sequence_mask(ctx, op):
    x = ctx.in1(op, "X")
    maxlen = op.attr("maxlen", -1)
    if maxlen is None or maxlen < 0:
        maxlen = op.attr("static_maxlen")
    dtype = _np_dtype(op.attr("out_dtype", "float32"))
    mask = (jnp.arange(maxlen)[None, :] < x.reshape(-1, 1)).astype(dtype)
    ctx.set_out(op, "Y", mask.reshape(tuple(x.shape) + (maxlen,)))


@register("delete_var")
def _delete_var(ctx, op):
    for n in op.input("X"):
        ctx.env.pop(n, None)


@register("print")
def _print(ctx, op):
    x = ctx.in1(op, "In")
    jax.debug.print(op.attr("message", "") + " {}", x)
    ctx.set_out(op, "Out", x)
