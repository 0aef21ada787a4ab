"""NN ops: softmax, dropout, normalization.

Reference parity: operators/{softmax,dropout,batch_norm,layer_norm,lrn,
maxout}_op.cc. batch_norm keeps running stats as persistable state threaded
through the step function (the reference mutates scope vars in-place;
functional state threading is the XLA equivalent).
"""

import jax
import jax.numpy as jnp

from ..core.registry import register
from ..monitor import metrics as _metrics
from .rotary import norm_rope


@register("softmax")
def _softmax(ctx, op):
    x = ctx.in1(op, "X")
    # AMP: exponentials/normalization in fp32, result back to input dtype
    xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
    ctx.set_out(op, "Out", jax.nn.softmax(xf, axis=-1).astype(x.dtype))


@register("log_softmax")
def _log_softmax(ctx, op):
    ctx.set_out(op, "Out", jax.nn.log_softmax(ctx.in1(op, "X"), axis=-1))


@register("sequence_softmax")
def _sequence_softmax(ctx, op):
    # softmax over each sequence segment; lengths come in via <X>@LOD
    x = ctx.in1(op, "X")
    lod_name = op.input("X")[0] + "@LOD"
    lengths = ctx.maybe_get(lod_name)
    if lengths is None:
        ctx.set_out(op, "Out", jax.nn.softmax(x.reshape(-1), axis=0).reshape(x.shape))
        return
    # segment softmax on flattened [T] data
    seg = _lengths_to_segments(lengths, x.shape[0])
    flat = x.reshape(x.shape[0])
    m = jax.ops.segment_max(flat, seg, num_segments=lengths.shape[0])
    e = jnp.exp(flat - m[seg])
    s = jax.ops.segment_sum(e, seg, num_segments=lengths.shape[0])
    ctx.set_out(op, "Out", (e / s[seg]).reshape(x.shape))


def _lengths_to_segments(lengths, total):
    ends = jnp.cumsum(lengths)
    return jnp.searchsorted(ends, jnp.arange(total), side="right")


@register("dropout", stateful_rng=True)
def _dropout(ctx, op):
    x = ctx.in1(op, "X")
    p = op.attr("dropout_prob", 0.5)
    is_test = op.attr("is_test", False) or ctx.is_test
    impl = op.attr("dropout_implementation", "downgrade_in_infer")
    if is_test or p == 0.0:
        # downgrade_in_infer scales at inference time (reference default)
        out = x * (1.0 - p) if (impl == "downgrade_in_infer" and p > 0.0) \
            else x
        ctx.set_out(op, "Out", out)
        ctx.set_out(op, "Mask", jnp.ones_like(x))
        return
    keep = 1.0 - p
    mask = jax.random.bernoulli(ctx.rng(), keep, x.shape).astype(x.dtype)
    ctx.set_out(op, "Mask", mask)
    if impl == "upscale_in_train":
        ctx.set_out(op, "Out", x * mask / keep)
    else:
        ctx.set_out(op, "Out", x * mask)


@register("batch_norm")
def _batch_norm(ctx, op):
    x = ctx.in1(op, "X")
    scale = ctx.in1(op, "Scale")
    bias = ctx.in1(op, "Bias")
    mean_in = ctx.in1(op, "Mean")
    var_in = ctx.in1(op, "Variance")
    eps = op.attr("epsilon", 1e-5)
    momentum = op.attr("momentum", 0.9)
    layout = op.attr("data_layout", "NCHW")
    is_test = op.attr("is_test", False) or ctx.is_test

    ch_axis = 1 if layout == "NCHW" and x.ndim > 1 else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]

    # stats in fp32 regardless of activation dtype (bf16 under AMP): a
    # bf16 accumulation over B*H*W elements loses the mean entirely.
    # ONE fused pass computes E[x] and E[x^2] together (vs mean-then-var's
    # second centered pass) — BN is the HBM-bandwidth tax of ResNet
    # training (~1/3 of step time at bs256), so activation reads are
    # minimized: stats read x once, normalization reads it once more with
    # the per-channel affine pre-folded in x's own dtype.
    if is_test:
        mean, var = mean_in, var_in
    else:
        n = 1
        for a in reduce_axes:
            n *= x.shape[a]
        # shifted one-pass stats: center on the RUNNING mean so the
        # E[x^2]-E[x]^2 form never cancels catastrophically (with c near
        # the true mean, s2/n ~ var instead of var + mean^2). Exact for
        # any c: var = E[(x-c)^2] - (E[x-c])^2, mean = c + E[x-c].
        # (Round-4 note: a raw-sum variant with the shift applied on
        # the [C] results measured NO faster on the real model — the
        # stat pass is structural XLA behavior with residual-block
        # consumers, not a artifact of this x - c form; see PERF.md
        # "ResNet conv+BN fusion probe".)
        xf = x.astype(jnp.float32)
        c = jax.lax.stop_gradient(mean_in.reshape(bshape)
                                  .astype(jnp.float32))
        xc = xf - c
        s1 = jnp.sum(xc, axis=reduce_axes)
        s2 = jnp.sum(jnp.square(xc), axis=reduce_axes)
        d1 = s1 / n
        mean = mean_in + d1
        var = jnp.maximum(s2 / n - jnp.square(d1), 0.0)
        new_mean = momentum * mean_in + (1 - momentum) * mean
        new_var = momentum * var_in + (1 - momentum) * var
        ctx.set_out(op, "MeanOut", new_mean)
        ctx.set_out(op, "VarianceOut", new_var)
        ctx.set_out(op, "SavedMean", mean)
        ctx.set_out(op, "SavedVariance", 1.0 / jnp.sqrt(var + eps))
        # MeanOut/VarianceOut alias Mean/Variance in the reference; keep the
        # state var updated under its own name too.
        min_names = op.input("Mean")
        vin_names = op.input("Variance")
        if min_names:
            ctx.env[min_names[0]] = jax.lax.stop_gradient(new_mean)
        if vin_names:
            ctx.env[vin_names[0]] = jax.lax.stop_gradient(new_var)

    # fold (mean, var, scale, bias) into one per-channel FMA applied in the
    # activation's own dtype: y = x * a + b — bf16 activations never make
    # an fp32 round-trip through HBM
    inv = jax.lax.rsqrt(var + eps)
    a = (scale * inv).astype(x.dtype)
    b = (bias - mean * scale * inv).astype(x.dtype)
    out = x * a.reshape(bshape) + b.reshape(bshape)
    ctx.set_out(op, "Y", out)


@register("layer_norm")
def _layer_norm(ctx, op):
    x = ctx.in1(op, "X")
    scale = ctx.in1(op, "Scale")
    bias = ctx.in1(op, "Bias")
    eps = op.attr("epsilon", 1e-5)
    begin = op.attr("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        out = out * scale.reshape((1,) * begin + x.shape[begin:])
    if bias is not None:
        out = out + bias.reshape((1,) * begin + x.shape[begin:])
    ctx.set_out(op, "Y", out.astype(x.dtype))
    ctx.set_out(op, "Mean", mean.reshape(x.shape[:begin]))
    ctx.set_out(op, "Variance", var.reshape(x.shape[:begin]))


@register("lrn")
def _lrn(ctx, op):
    x = ctx.in1(op, "X")                 # NCHW
    n = op.attr("n", 5)
    k = op.attr("k", 2.0)
    alpha = op.attr("alpha", 1e-4)
    beta = op.attr("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    ctx.set_out(op, "MidOut", mid)
    ctx.set_out(op, "Out", x / jnp.power(mid, beta))


@register("maxout")
def _maxout(ctx, op):
    x = ctx.in1(op, "X")                 # [N, C, H, W]
    groups = op.attr("groups")
    n, c, h, w = x.shape
    ctx.set_out(op, "Out",
                x.reshape(n, c // groups, groups, h, w).max(axis=2))


@register("im2sequence")
def _im2sequence(ctx, op):
    """Image → sequence of flattened patches (operators/im2sequence_op.cc)."""
    x = ctx.in1(op, "X")                 # [N, C, H, W]
    kh, kw = op.attr("kernels", [1, 1])
    sh, sw = op.attr("strides", [1, 1])
    pads = op.attr("paddings", [0, 0, 0, 0])
    x = jnp.pad(x, ((0, 0), (0, 0), (pads[0], pads[2]), (pads[1], pads[3])))
    n, c, h, w = x.shape
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1
    # HIGHEST precision: pure data movement (a one-hot conv) — the TPU
    # default bf16 MXU pass would quantize the copied pixel values
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)          # [N, C*kh*kw, oh, ow]
    seq = patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, c * kh * kw)
    ctx.set_out(op, "Out", seq)


# ---------------------------------------------------------------------------
# the pre-norm block's pieces (ISSUE 32): RMSNorm, rotary embedding and
# the gated FFN's product. Each computes in float32 and hands back x's
# dtype, so that under AMP a bfloat16 activation stays one. The norm and
# the rotation work on x as it comes, [.., H*D] (ops/rotary.py: one
# kernel pair where a head is whole lane tiles, no heads' view anywhere).
@register("rms_norm")
def _rms_norm(ctx, op):
    """X [..., G * D] with Scale [D]: RMSNorm over each of the G groups
    of D values of the last dimension (G 1: the whole of it; G the
    number of heads: QK-norm, one weight shared by the heads)."""
    x = ctx.in1(op, "X")
    scale = ctx.in1(op, "Scale")
    ctx.set_out(op, "Out", norm_rope(
        x, scale, x.shape[-1] // scale.shape[0],
        epsilon=op.attr("epsilon", 1e-6)))


@register("rope")
def _rope(ctx, op):
    """Rotary embedding, rotate-half form, of X [B, T, H * D] by each
    row's position: its index, taken modulo `wrap` where given."""
    ctx.set_out(op, "Out", norm_rope(
        ctx.in1(op, "X"), None, int(op.attr("n_head")),
        float(op.attr("theta", 10000.0)), int(op.attr("wrap", 0))))


@register("qk_norm_rope")
def _qk_norm_rope(ctx, op):
    """rope(rms_norm(X)) over the `n_head` heads of X [B, T, H * D]
    under one Scale [D], float32 from end to end; the norm alone where
    the attr `rotate` is false (a layer with no position signal)."""
    ctx.set_out(op, "Out", norm_rope(
        ctx.in1(op, "X"), ctx.in1(op, "Scale"), int(op.attr("n_head")),
        float(op.attr("theta", 10000.0)) if op.attr("rotate", True)
        else None, int(op.attr("wrap", 0)), op.attr("epsilon", 1e-6)))


# `silu_mul` under a written backward whose results are VALUES (ISSUE
# 67). As four lines of jax.numpy under autodiff, XLA's TPU pipeline
# makes the activation, and its gradient, AGAIN inside the kernel of
# each product that reads them (a nested fusion on the product's
# operand): `ffn_down`'s weight gradient then waits on a sigmoid and
# three operand streams where it would stream one, and ran at 35-42% of
# the peak. Here the forward's result and the backward's two gradients
# pass through `optimization_barrier`: no reader may make them again, so
# every product of the MLP reads plain arrays, and XLA makes them in the
# epilogue of the product before or in a loop fusion of their own. The
# residuals are X and Y as they came (in a recompute region its kept
# `mul_out`s): no float32 `[T, F]` and no `hidden` is saved.
_REG = _metrics.registry()
_SILU_MUL_LOWERINGS = _REG.counter(
    "ptpu_silu_mul_lowerings_total",
    "the gated FFN's activation (the Program op silu_mul) traced (one a "
    "lowering of a direction, none a step): the path (rule: the written "
    "backward whose results are values, the only one), the direction "
    "and the width",
    ("path", "direction", "width"))


@jax.custom_vjp
def silu_mul(x, y):
    """``silu(x) * y``, float32 inside, x's dtype out."""
    return _silu_mul_fwd(x, y)[0]


def _silu_mul_fwd(x, y):
    _SILU_MUL_LOWERINGS.inc(path="rule", direction="fwd",
                            width=str(x.shape[-1]))
    # (jax.nn.silu's own arithmetic, not the jitted function: a region
    # whose kept X is float32 would save a call's result beside it)
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.logistic(xf) * y.astype(jnp.float32)
    return jax.lax.optimization_barrier(out.astype(x.dtype)), (x, y)


def _silu_mul_bwd(res, d):
    x, y = res
    _SILU_MUL_LOWERINGS.inc(path="rule", direction="bwd",
                            width=str(x.shape[-1]))
    f32 = jnp.float32
    xf, d = x.astype(f32), d.astype(f32)
    s = jax.lax.logistic(xf)
    xs = xf * s
    dx = d * y.astype(f32) * (s + xs * (1 - s))
    return jax.lax.optimization_barrier(
        (dx.astype(x.dtype), (d * xs).astype(y.dtype)))


silu_mul.defvjp(_silu_mul_fwd, _silu_mul_bwd)


@register("silu_mul")
def _silu_mul(ctx, op):
    """silu(X) * Y: the gated FFN's hidden activation."""
    ctx.set_out(op, "Out", silu_mul(ctx.in1(op, "X"), ctx.in1(op, "Y")))


@register("sigmoid_mul")
def _sigmoid_mul(ctx, op):
    """X * sigmoid(Y): an output gate (attention's, ISSUE 38)."""
    x, y = ctx.in1(op, "X"), ctx.in1(op, "Y")
    out = x.astype(jnp.float32) * jax.nn.sigmoid(y.astype(jnp.float32))
    ctx.set_out(op, "Out", out.astype(x.dtype))
