"""Core math ops: mul/matmul (MXU path), reductions, scale, norms.

Reference parity: operators/mul_op.cc (x_num_col_dims flattening),
matmul_op.cc (batched + transpose flags), sum_op, mean_op, scale_op,
clip/clip_by_norm, reduce_op.cc family, cumsum, l1/l2 norms, cos_sim,
bilinear_tensor_product, top_k.

Matmuls accumulate in float32 via preferred_element_type so bf16 inputs use
the MXU at full throughput without losing accumulation precision.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .common import I64, lod_valid_mask
from ..core.registry import register


def _acc_type(x):
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    from ..amp import amp_enabled
    return jnp.float32 if amp_enabled() else None


def _amp_cast(*arrays):
    from ..amp import maybe_bf16
    return maybe_bf16(*arrays)


def _flatten2d(x, num_col_dims):
    lead = 1
    for s in x.shape[:num_col_dims]:
        lead *= s
    return x.reshape(lead, -1), x.shape


def mul_rows(op, x2, y, note=None):
    """The `mul` op's arithmetic on ROWS of its flattened X: x2 ``[rows,
    K]`` and Y as the op reads them -> out ``[rows, columns]``. The
    operands are cast as AMP (or the op's ``float32``) says, the product
    accumulated wide and rounded as `amp_out` rounds; `note`, where
    given, is told the product's sizes and its operands' dtype. X is
    flat BEFORE it is cast: cast as ``[B, T, K]`` at B over 1 and
    flattened after, XLA does not fuse what made X (a bias add, a ReLU)
    into the product's operand, and the value makes a float32 round
    trip through a relayout `copy` (PERF.md section 6, PR 61). A
    region's head in row blocks (ops/control_flow.py
    _loss_in_row_blocks) runs this a block at a time and transposes it
    for the product's two gradients."""
    float32 = op.attr("float32", False)           # amp.float32
    out_dtype = jnp.float32 if float32 else x2.dtype
    x2, y = (x2.astype(out_dtype), y.astype(out_dtype)) if float32 \
        else _amp_cast(x2, y)
    yn = op.attr("y_num_col_dims", 1)
    y2 = y.reshape(functools.reduce(lambda a, b: a * b, y.shape[:yn], 1), -1)
    if op.attr("transpose_Y", False):
        # a tied head: Y is the embedding's own [V, d] table, contracted
        # over its second dimension (nothing is transposed in memory)
        y2 = y2.T
    if float32:
        out = jnp.matmul(x2, y2, precision=lax.Precision.HIGHEST)
    else:
        from ..amp import amp_out
        out = amp_out(jnp.matmul(x2, y2, preferred_element_type=_acc_type(x2)),
                      out_dtype)
    if note is not None:
        note(mkn=x2.shape + out.shape[1:], operand_dtype=str(x2.dtype))
    return out


@register("mul")
def _mul(ctx, op):
    xn = op.attr("x_num_col_dims", 1)
    x2, xshape = _flatten2d(ctx.in1(op, "X"), xn)
    y = ctx.in1(op, "Y")
    out = mul_rows(op, x2, y, note=ctx.note)
    columns = out.shape[1:] if op.attr("transpose_Y", False) \
        else y.shape[op.attr("y_num_col_dims", 1):]
    ctx.set_out(op, "Out", out.reshape(xshape[:xn] + columns))


@register("matmul")
def _matmul(ctx, op):
    x = ctx.in1(op, "X")
    y = ctx.in1(op, "Y")
    out_dtype = x.dtype
    x, y = _amp_cast(x, y)
    if op.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if op.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    out = jnp.matmul(x, y, preferred_element_type=_acc_type(x))
    if out.ndim >= 2:
        # batched or not: 2 M K N is the product's FLOPs
        ctx.note(mkn=(out.size // out.shape[-1], x.shape[-1],
                      out.shape[-1]), operand_dtype=str(x.dtype))
    from ..amp import amp_out
    out = amp_out(out, out_dtype)
    alpha = op.attr("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    ctx.set_out(op, "Out", out)


@register("sum")
def _sum(ctx, op):
    xs = ctx.in_list(op, "X")
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    ctx.set_out(op, "Out", out)


@register("mean")
def _mean(ctx, op):
    x = ctx.in1(op, "X")
    out = _masked_mean(ctx, op, x, axes=None, keep=False)
    ctx.set_out(op, "Out", jnp.mean(x) if out is None else out)


@register("scale")
def _scale(ctx, op):
    x = ctx.in1(op, "X")
    if op.attr("float32", False):                 # amp.float32
        # a multiplier that bfloat16 does not hold (0.22 is 0.2197 there,
        # 0.12% less) on a product AMP handed on in bfloat16
        x = x.astype(jnp.float32)
    scale = op.attr("scale", 1.0)
    bias = op.attr("bias", 0.0)
    if op.attr("bias_after_scale", True):
        out = x * scale + bias
    else:
        out = (x + bias) * scale
    ctx.set_out(op, "Out", out)


@register("clip")
def _clip(ctx, op):
    x = ctx.in1(op, "X")
    ctx.set_out(op, "Out",
                jnp.clip(x, op.attr("min", -1.0), op.attr("max", 1.0)))


@register("clip_by_norm")
def _clip_by_norm(ctx, op):
    x = ctx.in1(op, "X")
    max_norm = op.attr("max_norm", 1.0)
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    ctx.set_out(op, "Out",
                jnp.where(norm > max_norm, x * (max_norm / norm), x))


def _row_mask(valid, x):
    return valid.reshape((x.shape[0],) + (1,) * (x.ndim - 1))


def _fill_value(fill, dtype):
    """dtype-preserving neutral element ('min'/'max' map to the dtype's
    extremes so integer reductions stay integer)."""
    if fill == "min":
        return jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer) \
            else -jnp.inf
    if fill == "max":
        return jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer) \
            else jnp.inf
    return jnp.asarray(fill, dtype)


def _masked_rows(ctx, op, x, fill=0):
    """x with bucket-pad rows replaced by the neutral `fill` (no-op when
    the input carries no LoD)."""
    valid, _ = lod_valid_mask(ctx, op)
    if valid is None:
        return x
    return jnp.where(_row_mask(valid, x), x, _fill_value(fill, x.dtype))


def _masked_mean(ctx, op, x, axes, keep):
    """Mean over the REAL rows of a bucketed LoD input (None when the
    input carries no LoD and the plain mean applies)."""
    valid, n_valid = lod_valid_mask(ctx, op)
    if valid is None:
        return None
    red = tuple(range(x.ndim)) if axes is None else axes
    other = 1
    for a in red:
        if a != 0:
            other *= x.shape[a]
    s = jnp.sum(jnp.where(_row_mask(valid, x), x, 0), axis=axes,
                keepdims=keep)
    acc = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
    return s / (n_valid.astype(acc) * other)


def _reduce(fn, fill=None):
    def lower(ctx, op):
        x = ctx.in1(op, "X")
        dim = op.attr("dim", [0])
        if op.attr("reduce_all", False):
            axes = None
        else:
            if isinstance(dim, int):
                dim = [dim]
            axes = tuple(d % x.ndim for d in dim)
        keep = op.attr("keep_dim", False)
        if axes is None or 0 in axes:
            # bucketed LoD input: neutralize pad rows before reducing the
            # row axis (sum: 0; max/min: dtype extremes; prod: 1)
            if fn is jnp.mean:
                out = _masked_mean(ctx, op, x, axes, keep)
                if out is not None:
                    ctx.set_out(op, "Out", out)
                    return
            else:
                x = _masked_rows(ctx, op, x, fill)
        ctx.set_out(op, "Out", fn(x, axis=axes, keepdims=keep))
    return lower


register("reduce_sum", _reduce(jnp.sum, fill=0))
register("reduce_mean", _reduce(jnp.mean))
register("reduce_max", _reduce(jnp.max, fill="min"))
register("reduce_min", _reduce(jnp.min, fill="max"))
register("reduce_prod", _reduce(jnp.prod, fill=1))


@register("cumsum")
def _cumsum(ctx, op):
    x = ctx.in1(op, "X")
    axis = op.attr("axis", -1)
    out = jnp.cumsum(x, axis=axis)
    if op.attr("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    if op.attr("exclusive", False):
        out = out - x
    ctx.set_out(op, "Out", out)


@register("l1_norm")
def _l1_norm(ctx, op):
    x = _masked_rows(ctx, op, ctx.in1(op, "X"))
    ctx.set_out(op, "Out", jnp.sum(jnp.abs(x)))


@register("squared_l2_norm")
def _squared_l2_norm(ctx, op):
    x = _masked_rows(ctx, op, ctx.in1(op, "X"))
    ctx.set_out(op, "Out", jnp.sum(jnp.square(x)))


@register("squared_l2_distance")
def _squared_l2_distance(ctx, op):
    x = ctx.in1(op, "X")
    y = ctx.in1(op, "Y")
    d = x - y
    ctx.set_out(op, "sub_result", d)
    ctx.set_out(op, "Out", jnp.sum(jnp.square(d), axis=-1, keepdims=True))


@register("norm")
def _norm(ctx, op):
    x = ctx.in1(op, "X")
    axis = op.attr("axis", 1)
    eps = op.attr("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    ctx.set_out(op, "Norm", norm)
    ctx.set_out(op, "Out", x / norm)


@register("cos_sim")
def _cos_sim(ctx, op):
    x = ctx.in1(op, "X")
    y = ctx.in1(op, "Y")
    xn = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True))
    out = jnp.sum(x * y, axis=-1, keepdims=True) / (xn * yn + 1e-12)
    ctx.set_out(op, "Out", out)
    ctx.set_out(op, "XNorm", xn)
    ctx.set_out(op, "YNorm", yn)


@register("bilinear_tensor_product")
def _bilinear(ctx, op):
    x = ctx.in1(op, "X")          # [B, M]
    y = ctx.in1(op, "Y")          # [B, N]
    w = ctx.in1(op, "Weight")     # [O, M, N]
    out = jnp.einsum("bm,omn,bn->bo", x, w, y)
    b = ctx.in1(op, "Bias")
    if b is not None:
        out = out + b
    ctx.set_out(op, "Out", out)


@register("top_k")
def _top_k(ctx, op):
    x = ctx.in1(op, "X")
    k = op.attr("k", 1)
    vals, idx = lax.top_k(x, k)
    ctx.set_out(op, "Out", vals)
    ctx.set_out(op, "Indices", idx.astype(I64()))


@register("arg_max")
def _arg_max(ctx, op):
    ctx.set_out(op, "Out", jnp.argmax(
        ctx.in1(op, "X"), axis=op.attr("axis", -1)).astype(I64()))


@register("arg_min")
def _arg_min(ctx, op):
    ctx.set_out(op, "Out", jnp.argmin(
        ctx.in1(op, "X"), axis=op.attr("axis", -1)).astype(I64()))


@register("minus")
def _minus(ctx, op):
    ctx.set_out(op, "Out", ctx.in1(op, "X") - ctx.in1(op, "Y"))


@register("conv_shift")
def _conv_shift(ctx, op):
    # circular correlation (operators/conv_shift_op.cc)
    x = ctx.in1(op, "X")          # [B, M]
    y = ctx.in1(op, "Y")          # [B, N], N odd, N <= M
    m = x.shape[1]
    n = y.shape[1]
    half = n // 2
    idx = (jnp.arange(m)[:, None] + jnp.arange(-half, half + 1)[None, :]) % m
    gathered = x[:, idx]                     # [B, M, N]
    ctx.set_out(op, "Out", jnp.einsum("bmn,bn->bm", gathered, y))
