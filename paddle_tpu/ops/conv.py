"""Convolution / pooling / vision ops.

Reference parity: operators/conv_op.cc (+cudnn), conv_transpose_op.cc,
pool_op.cc, pool_with_index_op.cc, unpool_op.cc, spp_op.cc, roi_pool_op.cc,
row_conv_op.cc, operators/math/{im2col,vol2col,pooling,depthwise_conv}.

TPU-first: every conv lowers to a single ``lax.conv_general_dilated`` — the
op XLA tiles directly onto the MXU — instead of the reference's
im2col+GEMM / cuDNN dispatch. Data layout attr is honoured (NCHW default for
API parity); XLA relayouts internally for the TPU's preferred tiling, so no
manual NHWC conversion is needed. Grouped and depthwise convs use
``feature_group_count`` (no separate depthwise kernel like
math/depthwise_conv.cu).
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


def _conv_dnums(ndim, layout):
    # (lhs, rhs, out) dimension-number strings for 1/2/3-d convs.
    sp = "DHW"[-(ndim - 2):] if ndim > 2 else ""
    if layout == "NHWC":
        lhs = "N" + sp + "C"
    else:
        lhs = "NC" + sp
    return lax.conv_dimension_numbers((1,) * ndim, (1,) * ndim,
                                      (lhs, "OI" + sp, lhs))


def _conv_nd(ctx, op, ndim):
    x = ctx.in1(op, "Input")
    w = ctx.in1(op, "Filter")
    out_dtype = x.dtype
    from ..amp import maybe_bf16
    x, w = maybe_bf16(x, w)
    strides = _pair(op.attr("strides", [1] * (ndim - 2)), ndim - 2)
    paddings = _pair(op.attr("paddings", [0] * (ndim - 2)), ndim - 2)
    dilations = _pair(op.attr("dilations", [1] * (ndim - 2)), ndim - 2)
    groups = int(op.attr("groups", 1) or 1)
    layout = op.attr("data_format", op.attr("data_layout", "NCHW"))
    layout = "NHWC" if layout in ("NHWC", "NDHWC") else "NCHW"
    dn = _conv_dnums(ndim, layout)
    pad = [(p, p) for p in paddings]
    # bf16 path: all-bf16 with pet=None. On TPU the MXU accumulates bf16
    # dots in fp32 internally regardless of preferred_element_type (pet only
    # selects the RESULT dtype), and an explicit fp32 pet breaks jax's conv
    # vjp on bf16 inputs (mixed-dtype transpose conv) — so bf16 training
    # requires this form; only the final rounding to bf16 differs.
    pet = None if x.dtype == jnp.bfloat16 else (
        x.dtype if x.dtype == jnp.float64 else jnp.float32)
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad,
        lhs_dilation=(1,) * (ndim - 2), rhs_dilation=dilations,
        dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=pet)
    from ..amp import amp_out
    ctx.set_out(op, "Output", amp_out(out, out_dtype))


@register("conv2d")
def _conv2d(ctx, op):
    _conv_nd(ctx, op, 4)


@register("conv3d")
def _conv3d(ctx, op):
    _conv_nd(ctx, op, 5)


@register("depthwise_conv2d")
def _depthwise_conv2d(ctx, op):
    # filter [C*mult, 1, kh, kw], groups == C (conv_op.cc depthwise path)
    x = ctx.in1(op, "Input")
    op.attrs = dict(op.attrs)
    op.attrs["groups"] = int(x.shape[1])
    _conv_nd(ctx, op, 4)


def _conv_transpose_nd(ctx, op, ndim):
    # Reference filter layout [C_in, C_out/groups, kH, kW]
    # (conv_transpose_op.cc). Lower as the gradient-of-conv: input dilation.
    x = ctx.in1(op, "Input")
    w = ctx.in1(op, "Filter")
    nsp = ndim - 2
    strides = _pair(op.attr("strides", [1] * nsp), nsp)
    paddings = _pair(op.attr("paddings", [0] * nsp), nsp)
    dilations = _pair(op.attr("dilations", [1] * nsp), nsp)
    groups = int(op.attr("groups", 1) or 1)
    out_dtype = x.dtype
    from ..amp import maybe_bf16
    x, w = maybe_bf16(x, w)
    # transpose-conv == conv with lhs_dilation=stride, flipped kernel,
    # padding (k-1)*d - p on each side
    sp_axes = tuple(range(2, ndim))
    w_flip = jnp.flip(w, sp_axes)
    # [Cin, Cout/g, k...] -> [Cout, Cin/g, k...]
    if groups == 1:
        w_t = jnp.swapaxes(w_flip, 0, 1)
    else:
        cin, cog = w.shape[0], w.shape[1]
        w_g = w_flip.reshape((groups, cin // groups, cog) + w.shape[2:])
        w_g = jnp.swapaxes(w_g, 1, 2)  # [g, cog, cin/g, k...]
        w_t = w_g.reshape((groups * cog, cin // groups) + w.shape[2:])
    pad = [((w.shape[2 + i] - 1) * dilations[i] - paddings[i],) * 2
           for i in range(nsp)]
    dn = _conv_dnums(ndim, "NCHW")
    out = lax.conv_general_dilated(
        x, w_t, window_strides=(1,) * nsp, padding=pad,
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=dn, feature_group_count=groups)
    out_size = op.attr("output_size")
    if out_size:
        # Paddle allows output_size in [minimal, minimal+stride): shrink by
        # slicing, enlarge by bottom/right zero-pad (conv_transpose_op.cc).
        out = out[(Ellipsis,) + tuple(slice(0, int(s)) for s in out_size)]
        pad = [(0, 0), (0, 0)] + [
            (0, max(0, int(s) - out.shape[2 + i]))
            for i, s in enumerate(out_size)]
        out = jnp.pad(out, pad)
    from ..amp import amp_out
    ctx.set_out(op, "Output", amp_out(out, out_dtype))


@register("conv2d_transpose")
def _conv2d_transpose(ctx, op):
    _conv_transpose_nd(ctx, op, 4)


@register("conv3d_transpose")
def _conv3d_transpose(ctx, op):
    _conv_transpose_nd(ctx, op, 5)


# --------------------------------------------------------------------------
# pooling
# --------------------------------------------------------------------------

def _pool_out(x, ksize, strides, paddings, pooling_type, ceil_mode,
              exclusive, global_pooling, adaptive):
    n_sp = len(ksize)
    sp_shape = x.shape[2:]
    if global_pooling:
        ksize = tuple(sp_shape)
        paddings = (0,) * n_sp
        strides = tuple(sp_shape)
    if adaptive:
        return _adaptive_pool(x, ksize, pooling_type)
    window = (1, 1) + tuple(ksize)
    strd = (1, 1) + tuple(strides)
    if ceil_mode:
        # pad the right edge so the last partial window is included
        extra = []
        for i in range(n_sp):
            span = sp_shape[i] + 2 * paddings[i] - ksize[i]
            rem = span % strides[i]
            extra.append((strides[i] - rem) % strides[i] if rem else 0)
        pad = [(0, 0), (0, 0)] + [(paddings[i], paddings[i] + extra[i])
                                  for i in range(n_sp)]
    else:
        pad = [(0, 0), (0, 0)] + [(p, p) for p in paddings]

    if pooling_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strd, pad)
    # avg
    s = lax.reduce_window(x.astype(jnp.float32), 0.0, lax.add, window, strd,
                          pad)
    if exclusive or any(p[0] or p[1] for p in pad[2:]):
        ones = jnp.ones(x.shape, jnp.float32)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strd, pad)
        if not exclusive:
            cnt = jnp.maximum(cnt, float(np.prod(ksize)))
        out = s / jnp.maximum(cnt, 1.0)
    else:
        out = s / float(np.prod(ksize))
    return out.astype(x.dtype)


def _adaptive_pool(x, out_sz, pooling_type):
    """Adaptive pooling with Paddle's bin rule: bin i spans
    [floor(i*S/o), ceil((i+1)*S/o)) (pool_op.cc AdaptiveStartIndex/EndIndex).
    Lowered as per-axis mask reductions so the output size is exact for
    non-divisible sizes too."""
    sp_shape = x.shape[2:]
    out = x
    for ax, (size, o) in enumerate(zip(sp_shape, out_sz)):
        i = np.arange(o)
        starts = (i * size) // o
        ends = -(-((i + 1) * size) // o)
        pos = np.arange(size)
        mask = (pos[None, :] >= starts[:, None]) & (pos[None, :] < ends[:, None])
        axis = 2 + ax
        # move target axis last, reduce against mask, put bin axis back
        moved = jnp.moveaxis(out, axis, -1)[..., None, :]    # [..., 1, S]
        m = jnp.asarray(mask)                                # [o, S]
        if pooling_type == "max":
            red = jnp.max(jnp.where(m, moved, -jnp.inf), axis=-1)
        else:
            cnt = (ends - starts).astype(np.float32)
            red = jnp.sum(jnp.where(m, moved, 0.0), axis=-1) / \
                jnp.asarray(cnt, out.dtype)
        out = jnp.moveaxis(red, -1, axis)
    return out.astype(x.dtype)


def _pool_nd(ctx, op, n_sp):
    x = ctx.in1(op, "X")
    ksize = _pair(op.attr("ksize", [1] * n_sp), n_sp)
    strides = _pair(op.attr("strides", [1] * n_sp), n_sp)
    paddings = _pair(op.attr("paddings", [0] * n_sp), n_sp)
    if op.attr("adaptive", False):
        out = _adaptive_pool(x, ksize, op.attr("pooling_type", "max"))
    else:
        out = _pool_out(x, ksize, strides, paddings,
                        op.attr("pooling_type", "max"),
                        op.attr("ceil_mode", False),
                        op.attr("exclusive", True),
                        op.attr("global_pooling", False), False)
    ctx.set_out(op, "Out", out)


@register("pool2d")
def _pool2d(ctx, op):
    _pool_nd(ctx, op, 2)


@register("pool3d")
def _pool3d(ctx, op):
    _pool_nd(ctx, op, 3)


def _extract_patches(x, ksize, strides, paddings):
    """[N,C,H,W] -> (patches [N,C,kh*kw,Ho,Wo], flat spatial index of each
    patch element [N,C,kh*kw,Ho,Wo]). Padding is applied here with -inf on
    values (so pad cells never win a max) and -1 on indices."""
    n, c, h, w = x.shape
    kh, kw = ksize
    ph, pw = paddings
    # finite lowest value, not -inf: patch extraction is a one-hot conv and
    # -inf * 0 would poison every patch with NaN
    if jnp.issubdtype(x.dtype, jnp.floating):
        lowest = float(jnp.finfo(x.dtype).min)
    else:
        lowest = int(jnp.iinfo(x.dtype).min)
    xp = jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)),
                 constant_values=lowest)
    # HIGHEST precision: patch extraction is pure data movement (a
    # one-hot conv); the TPU's default bf16 MXU pass would QUANTIZE the
    # copied values, corrupting pooled maxima
    patches = lax.conv_general_dilated_patches(
        xp, filter_shape=ksize, window_strides=strides,
        padding=[(0, 0), (0, 0)], dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)
    # patches: [N, C*kh*kw, Ho, Wo]
    ho, wo = patches.shape[2], patches.shape[3]
    patches = patches.reshape(n, c, kh * kw, ho, wo)
    # analytic index map (exact int32; a float conv would lose precision
    # above 2**24): element (ki,kj) of the patch at output (oh,ow) sits at
    # input position (oh*sh - ph + ki, ow*sw - pw + kj)
    sh, sw = strides
    oh = jnp.arange(ho)[:, None, None, None]
    ow = jnp.arange(wo)[None, :, None, None]
    ki = jnp.arange(kh)[None, None, :, None]
    kj = jnp.arange(kw)[None, None, None, :]
    iy = oh * sh - ph + ki
    ix = ow * sw - pw + kj
    valid = (iy >= 0) & (iy < h) & (ix >= 0) & (ix < w)
    flat = jnp.where(valid, iy * w + ix, -1)        # [Ho, Wo, kh, kw]
    ipatch = jnp.transpose(flat.reshape(ho, wo, kh * kw), (2, 0, 1))
    ipatch = ipatch[None, None].astype(jnp.int32)   # [1,1,kh*kw,Ho,Wo]
    return patches, ipatch


@register("max_pool2d_with_index")
def _max_pool2d_with_index(ctx, op):
    # pool_with_index_op.cc: returns pooled values + flat spatial argmax
    x = ctx.in1(op, "X")
    ksize = _pair(op.attr("ksize", [2, 2]))
    strides = _pair(op.attr("strides", [2, 2]))
    paddings = _pair(op.attr("paddings", [0, 0]))
    if op.attr("global_pooling", False):
        ksize = x.shape[2:]
        strides = ksize
        paddings = (0, 0)
    patches, ipatch = _extract_patches(x, ksize, strides, paddings)
    amax = jnp.argmax(patches, axis=2)
    out = jnp.max(patches, axis=2)
    idx = jnp.take_along_axis(
        jnp.broadcast_to(ipatch, patches.shape[:2] + ipatch.shape[2:]),
        amax[:, :, None], axis=2)[:, :, 0]
    ctx.set_out(op, "Out", out)
    ctx.set_out(op, "Mask", idx.astype(jnp.int32))


@register("unpool")
def _unpool(ctx, op):
    # unpool_op.cc: scatter pooled values back to argmax positions
    x = ctx.in1(op, "X")
    mask = ctx.in1(op, "Indices")
    n, c, ho, wo = x.shape
    ksize = _pair(op.attr("ksize", [2, 2]))
    strides = _pair(op.attr("strides", ksize))
    paddings = _pair(op.attr("paddings", [0, 0]))
    # unpool_op.cc: H_out = (H_in-1)*stride - 2*pad + ksize
    h = (ho - 1) * strides[0] - 2 * paddings[0] + ksize[0]
    w = (wo - 1) * strides[1] - 2 * paddings[1] + ksize[1]
    flat = jnp.zeros((n, c, h * w), x.dtype)
    idx = mask.reshape(n, c, ho * wo).astype(jnp.int32)
    vals = x.reshape(n, c, ho * wo)
    out = jax.vmap(jax.vmap(lambda f, i, v: f.at[i].set(v)))(flat, idx, vals)
    ctx.set_out(op, "Out", out.reshape(n, c, h, w))


@register("spp")
def _spp(ctx, op):
    # spp_op.cc: spatial pyramid pooling — concat of pyramid_height adaptive
    # pools flattened per level
    x = ctx.in1(op, "X")
    levels = int(op.attr("pyramid_height", 1))
    ptype = op.attr("pooling_type", "max")
    n = x.shape[0]
    outs = []
    for lvl in range(levels):
        bins = 2 ** lvl
        h, w = x.shape[2], x.shape[3]
        kh, kw = -(-h // bins), -(-w // bins)
        sh, sw = kh, kw
        ph = max(0, (bins * kh - h + 1) // 2)
        pw = max(0, (bins * kw - w + 1) // 2)
        pooled = _pool_out(x, (kh, kw), (sh, sw), (ph, pw), ptype,
                           False, False, False, False)
        outs.append(pooled.reshape(n, -1))
    ctx.set_out(op, "Out", jnp.concatenate(outs, axis=1))


@register("roi_pool")
def _roi_pool(ctx, op):
    # roi_pool_op.cc: max-pool each ROI into pooled_h x pooled_w bins
    x = ctx.in1(op, "X")
    rois = ctx.in1(op, "ROIs")          # [R, 4] (x1,y1,x2,y2)
    ph = int(op.attr("pooled_height", 1))
    pw = int(op.attr("pooled_width", 1))
    scale = float(op.attr("spatial_scale", 1.0))
    n, c, h, w = x.shape
    r = rois.shape[0]
    lod = ctx.maybe_get(op.input("ROIs")[0] + "@LOD")
    if lod is not None:
        batch_idx = jnp.repeat(jnp.arange(lod.shape[0]), lod,
                               total_repeat_length=r)
    else:
        batch_idx = jnp.zeros((r,), jnp.int32)

    ys = jnp.arange(h, dtype=jnp.float32)
    xs = jnp.arange(w, dtype=jnp.float32)

    def one_roi(roi, bi):
        x1 = jnp.round(roi[0] * scale)
        y1 = jnp.round(roi[1] * scale)
        x2 = jnp.round(roi[2] * scale)
        y2 = jnp.round(roi[3] * scale)
        rh = jnp.maximum(y2 - y1 + 1, 1.0)
        rw = jnp.maximum(x2 - x1 + 1, 1.0)
        bh, bw = rh / ph, rw / pw
        img = x[bi]                                   # [C,H,W]

        def bin_val(i, j):
            ys0 = jnp.floor(y1 + i * bh)
            ys1 = jnp.ceil(y1 + (i + 1) * bh)
            xs0 = jnp.floor(x1 + j * bw)
            xs1 = jnp.ceil(x1 + (j + 1) * bw)
            my = (ys >= ys0) & (ys < jnp.maximum(ys1, ys0 + 1))
            mx = (xs >= xs0) & (xs < jnp.maximum(xs1, xs0 + 1))
            m = my[:, None] & mx[None, :]
            return jnp.max(jnp.where(m[None], img, -jnp.inf), axis=(1, 2))

        ii, jj = jnp.meshgrid(jnp.arange(ph), jnp.arange(pw), indexing="ij")
        vals = jax.vmap(jax.vmap(bin_val))(ii.astype(jnp.float32),
                                           jj.astype(jnp.float32))
        # vals: [ph, pw, C] -> [C, ph, pw]
        out = jnp.transpose(vals, (2, 0, 1))
        return jnp.where(jnp.isfinite(out), out, 0.0)

    out = jax.vmap(one_roi)(rois.astype(jnp.float32), batch_idx)
    ctx.set_out(op, "Out", out.astype(x.dtype))


@register("row_conv")
def _row_conv(ctx, op):
    # row_conv_op.cc: lookahead conv over time for each sequence.
    # x [T, D] flat sequences, filter [future_context+1, D].
    x = ctx.in1(op, "X")
    w = ctx.in1(op, "Filter")
    k = w.shape[0]
    lengths = ctx.maybe_get(op.input("X")[0] + "@LOD")
    xp = jnp.pad(x, ((0, k - 1), (0, 0)))
    stacked = jnp.stack([xp[i:i + x.shape[0]] for i in range(k)], axis=0)
    out = jnp.einsum("ktd,kd->td", stacked, w)
    if lengths is not None:
        # zero out lookahead crossing sequence boundaries
        ends = jnp.cumsum(lengths)
        seg = jnp.searchsorted(ends, jnp.arange(x.shape[0]), side="right")
        seg_p = jnp.pad(seg, (0, k - 1), constant_values=seg[-1] + 1 if
                        x.shape[0] else 0)
        contrib = jnp.stack(
            [jnp.where((seg_p[i:i + x.shape[0]] == seg)[:, None],
                       xp[i:i + x.shape[0]], 0.0) for i in range(k)], axis=0)
        out = jnp.einsum("ktd,kd->td", contrib, w)
    ctx.set_out(op, "Out", out)
