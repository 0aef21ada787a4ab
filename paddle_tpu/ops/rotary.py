"""Per-head RMSNorm and rotary embedding in the projections' own layout.

QK-norm and RoPE work on the heads of a projection's output. That
output is [B, T, H*D], and on the chip its last two dimensions are
tiled (8 rows of T, 128 lanes); the heads' view [B, T, H, D] is tiled
(8 HEADS, 128 lanes), so every reshape between the two is a physical
copy of the whole tensor, rotate-half's slices and concatenation are
more, 4 key/value heads pad to 8 sublanes, and autodiff repeats all of
it backward (41 + 23 ms of a 416 ms step in `sdar_train_bd4k`; PERF.md
section 5, PR 32). Where D is a multiple of 128 a head IS whole lane
tiles of the [B*T, H*D] view, so nothing has to move: the norm is a
lane reduction inside each head's lanes and rotate-half a roll by D/2
lanes inside them. Where D divides 128 (heads of 64: `lfm2_train_T32k`,
MLA's rotary query part) a lane tile IS 128 / D whole heads, and
nothing has to move either: the tile is the unit the body works on.

One kernel pair, `qk_norm_rope_fwd` and `qk_norm_rope_bwd` (named in
the trace). A grid step holds [rows, g*U] of the [B*T, H*D] view (a
leading-dimension collapse, free), g units of U = max(D, 128) lanes
that take turns, a unit one head or one lane tile of heads:

  forward   x -> float32; `inv = rsqrt(mean(x^2) + eps)` over the
            head's lanes; `y = x * inv * w` (w [D], resident); then
            `y * cos + roll(y, D/2) * sin_signed` against a float32
            [period, D] table of cos and sign-folded sin (-sin in the
            first half, +sin in the second) made ONCE an op outside the
            kernel; the block's row index modulo the period picks its
            table rows (period: `wrap` where it divides T, the noised
            and the clean half sharing positions 0..L-1; else T).
            Output in x's dtype.
  backward  the transposed rotation (the same table, sin negated), then
            RMSNorm's gradient with `inv` RECOMPUTED from the saved x
            (x is saved as it came: no float32 copy is kept), and dScale
            as per-block partial sums [blocks, 8, U] that XLA adds.
  a tile of the same body on [rows, 128]: the mean over EACH head's D
  heads     lanes is a masked lane reduction a head, put back on its
            lanes (`_head_mean`); the weight arrives tiled to 128 lanes;
            rotate-half inside D lanes is TWO rolls, by 128 - D/2 for a
            head's first half and by D/2 for its second, against two
            sign-folded sine tables each zero where the other applies
            (`rope_table`: `y * cos + roll(y, 128 - D/2) * sin_a +
            roll(y, D/2) * sin_b`, no select); dScale's partial sums
            hold the tile's heads side by side and XLA adds them with
            the blocks.

Static flags `norm` (a Scale is given) and `rotate` (a theta is given)
let the one body serve the fused op and each op alone, so the Program
ops `qk_norm_rope`, `rms_norm` (grouped) and `rope` all lower here.

Dispatch (`norm_rope`), from the shape alone: the kernel where there
are heads (or a rotation), D is a multiple of 128 or divides it with
H*D whole lane tiles, the rows can be cut into blocks and the backend
is a TPU; anything else (an odd number of heads of 64, MLA's ONE rotary
key head of 64, a head of 96, the CPU) takes the jax.numpy form
(`_xla`), float32 inside and x's dtype out like the kernel, with the
heads' view only where there are heads: one group (the stream's norms)
is computed on x as it comes, on every backend, and XLA fuses it with
its neighbours. Each dispatch
counts itself at trace time in `ptpu_rotary_lowerings_total{path,
heads, head_dim, norm, rotate}` (path: "pallas" / "interpret" / "xla").
"""

import functools

import jax
import jax.numpy as jnp

from ..monitor import metrics as _metrics
from .flash_attention import _largest_divisor, _on_tpu

_LANES = 128
# A grid step's block of x: at most _MAX_LANES lanes of at most
# _MAX_HEADS whole heads and _BLOCK_BYTES in x's dtype (the backward
# holds three such blocks, dy, x and dx, twice over for the double
# buffering, beside a head's float32
# temporaries, in 16 MB of scoped VMEM). Measured on one TPU v5e, q
# [2, 8192, 32 x 128] bf16 with the norm and the rotation, ms a call
# forward / backward (my chip run, PR 33; the floors at 819 GB/s are
# 0.328 / 0.492; the jax.numpy form on the heads' view 10.27 / 9.11):
#   [1024, 1024]  0.455  0.678      [128, 4096]  0.673  4.435
#   [512, 1024]   0.455  0.702      [64, 4096]   1.093  1.243
#   [256, 2048]   0.480  0.698      [256, 4096]  out of VMEM
#   [1024, 512]   0.459  0.700      [256, 1024]  0.541  0.756
#   [4096, 128]   0.472  0.704      [256, 512]   0.617  0.837
# So 512 rows or more of at most 1024 lanes: 32 heads taking turns in
# one block spill (the backward carries dScale's partial sums across
# them), and under 512 rows a grid step's hand-over shows. One group of
# 2048 float32 values a row ([2, 8192, 2048], the stream's norms) at
# [128, 2048]: 0.428 / 0.628 against XLA's 0.609 / 1.164; at [256, 2048]
# the backward's temporaries run out of VMEM.
# Heads smaller than a lane tile, two of 64 or four of 32 to 128 lanes
# (device ms under the profiler, norm and rotation, the forward's with
# the table's making; my chip runs, PR 50). x [1, 32768, 32 x 64] bf16,
# floors 0.328 / 0.492, by block, beside the seconds the chip's host
# took to trace and lower the forward, and the forward with the
# backward:
#   [512, 1024]  0.647  0.753  0.29  0.79    [256, 1024]  0.747  0.836
#   [1024, 512]  0.655  0.813  0.14  0.38    [256, 2048]  0.737  0.815
#   [2048, 256]  0.673  0.874  0.11  0.24    [1024, 1024] out of VMEM
# A tile's body is twice a head's to trace and lower, and with 16 heads
# of 64 to a block `lfm2_train_T32k`'s `setup_trace_lower_s.train` read
# 7.6 -> 10.1 s: so at most _MAX_HEADS heads take turns in a block,
# whatever their size (1024 lanes of 128 as before, 512 of 64), for
# 0.07 ms a step. At [512, 1024]: the heads' sums as masked lane
# reductions 0.647 / 0.753, as a product with a block-diagonal
# [128, 128] of ones on the MXU at `highest` 0.664 / 1.080, the
# jax.numpy form 7.098 / 5.266; k [1, 32768, 8 x 64] at [1024, 512]
# 0.260 / 0.221, 0.262 / 0.280, 1.189 / 0.994. Heads of 32,
# [1, 32768, 64 x 32] at [512, 1024]: 0.913 / 1.271, 0.648 / 1.080,
# 7.143 / 5.239: the product wins the forward there and no cell has
# such heads, so one form, the reductions. Alone at 32 x 64: the
# rotation 0.498 / 0.410 (jax.numpy 5.225 / 3.995), the norm 0.416 /
# 0.633 (2.904 / 2.945); MLA's query part [1, 4096, 32 x 64], the
# rotation by given frequencies, 0.056 / 0.055 (0.145 / 0.102). Heads
# under 32 lanes take the same body and were not timed.
_BLOCK_BYTES = 1024 * 1024
_MAX_LANES = 1024
_MAX_HEADS = 8

_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_rotary_lowerings_total",
    "per-head RMSNorm / rotary embedding dispatches at trace time (one a "
    "lowering of the op, none a step): the path taken, the heads and their "
    "size, and which of the two halves of the work the call asked for",
    ("path", "heads", "head_dim", "norm", "rotate"))


def _period(t, wrap):
    """Rows after which the positions repeat in the [B*T] view."""
    return wrap if wrap and t % wrap == 0 else t


def yarn_inv_freq(d, theta, factor, original_positions, beta_fast=32.0,
                  beta_slow=1.0):
    """YaRN's d / 2 blended frequencies as a tuple of floats: pair i
    turns at theta^(-2i/d) where it completes more than `beta_fast`
    turns over the `original_positions` (extrapolated), at 1/`factor`
    of that where fewer than `beta_slow` (interpolated), and on a
    linear ramp over the pairs between the two."""
    import math
    turns_at = lambda turns: d * math.log(
        original_positions / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), d - 1)
    out = []
    for i in range(d // 2):
        freq = theta ** (-2.0 * i / d)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(freq / factor * ramp + freq * (1.0 - ramp))
    return tuple(out)


def _angles(t, wrap, d, theta):
    """(cos, sin), float32 [period, d / 2], of the positions of the
    first `period` of t rows: a row's index, modulo `wrap` where
    given. `theta` is the base the d / 2 frequencies are made from, or
    the frequencies themselves, given (a sequence of d / 2 floats:
    YaRN's, `yarn_inv_freq`)."""
    pos = jnp.arange(_period(t, wrap))
    if wrap:
        pos = pos % wrap
    if isinstance(theta, (tuple, list)):
        inv_freq = jnp.asarray(theta, jnp.float32)
    else:
        inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def _unit(d):
    """The lanes the kernels handle at a time: a head, or the lane tile
    that holds 128 // d heads where a head divides one."""
    return _LANES if _LANES % d == 0 else d


def _shifts(d):
    """The lane rolls that bring rotate-half's partner to each position
    of a unit, one a sine table of `rope_table`: d / 2 where the unit
    is the head, the roll its own inverse; in a lane tile of several
    heads a first half takes `y[p + d/2]`, a roll by 128 - d / 2, and a
    second half `y[p - d/2]`."""
    return (d // 2,) if _unit(d) == d else (_unit(d) - d // 2, d // 2)


def rope_table(t, wrap, d, theta):
    """The kernels' table, float32 [period, unit], in rotate-half form.
    A head that is its own unit: (cos, sin_signed), both halves of d
    holding the same angles and sin carrying rotate-half's sign (-sin,
    +sin), so that `y * cos + roll(y, d/2) * sin_signed` is the
    rotation. A lane tile of several heads: (cos, sin_a, sin_b), the
    heads side by side, sin_a -sin in the first half of every head and
    0 in the second, sin_b 0 and +sin, each zero where the other's roll
    applies (`_shifts`), so that no select is needed."""
    cos, sin = _angles(t, wrap, d, theta)
    if _unit(d) == d:
        return (jnp.concatenate([cos, cos], -1),
                jnp.concatenate([-sin, sin], -1))
    zero = jnp.zeros_like(sin)
    return tuple(jnp.tile(jnp.concatenate(halves, -1), (1, _unit(d) // d))
                 for halves in ((cos, cos), (-sin, zero), (zero, sin)))


def _xla(x, scale, n_head, theta, wrap, epsilon):
    """The jax.numpy form: float32 inside, x's dtype out."""
    hd = x.shape[-1]
    d = hd // n_head
    y = x.astype(jnp.float32)
    if n_head > 1:
        y = y.reshape(x.shape[:-1] + (n_head, d))
    if scale is not None:
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                            + epsilon)
        y = y * inv * scale.astype(jnp.float32)
    if theta is not None:
        t = x.shape[-2]
        cos, sin = (jnp.tile(half, (t // half.shape[0], 2))
                    for half in _angles(t, wrap, d, theta))
        if n_head > 1:
            cos, sin = cos[:, None], sin[:, None]
        turned = jnp.concatenate([-y[..., d // 2:], y[..., :d // 2]], -1)
        y = y * cos + turned * sin
    return y.reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# the kernels. refs, in order: x [w] [table] -> out forward, and
# dy [x w] [table] -> dx [dw] backward; a unit is the lanes
# [a u, (a + 1) u) of the block, static slices of whole lane tiles: one
# head, or the 128 // d heads of a lane tile.
def _take(refs, norm, rotate, d):
    """(the weight where `norm`, the table where `rotate`, the refs
    left)."""
    refs = list(refs)
    w = refs.pop(0)[...] if norm else None
    table = [refs.pop(0)[...] for _ in range(1 + len(_shifts(d)))] \
        if rotate else None
    return w, table, refs


def _head_mean(v, d):
    """The mean of v [rows, unit] over each head's lanes: [rows, 1]
    where the unit is the head; in a lane tile of several, [rows, 128]
    with every lane holding its own head's: one masked lane reduction a
    head (at two heads of 64 faster than `v` times a block-diagonal
    matrix of ones on the idle MXU at `highest`: the comment at
    _BLOCK_BYTES)."""
    if v.shape[-1] == d:
        return jnp.mean(v, -1, keepdims=True)
    head = jax.lax.broadcasted_iota(jnp.int32, (1, v.shape[-1]), 1) // d
    sums = None
    for a in range(v.shape[-1] // d):
        own = jnp.sum(jnp.where(head == a, v, 0.0), -1, keepdims=True)
        sums = own if sums is None else jnp.where(head == a, own, sums)
    return sums * (1.0 / d)


def _inv_rms(x, d, eps):
    return jax.lax.rsqrt(_head_mean(x * x, d) + eps)


def _turned(y, table, d, back=False):
    """A unit turned by its rows' angles; `back`, by the angles negated
    (the transposed rotation): the same rolls and tables, the sines
    subtracted."""
    out = y * table[0]
    for shift, sin in zip(_shifts(d), table[1:]):
        partner = pltpu.roll(y, shift, 1) * sin
        out = out - partner if back else out + partner
    return out


def _fwd_kernel(x_ref, *refs, norm, rotate, d, g, eps):
    w, table, (o_ref,) = _take(refs, norm, rotate, d)
    u = _unit(d)
    for a in range(g):
        lanes = slice(a * u, (a + 1) * u)
        y = x_ref[:, lanes].astype(jnp.float32)
        if norm:
            y = y * _inv_rms(y, d, eps) * w
        if rotate:
            y = _turned(y, table, d)
        o_ref[:, lanes] = y.astype(o_ref.dtype)


def _bwd_kernel(dy_ref, *refs, norm, rotate, d, g, eps):
    x_ref = refs[0] if norm else None
    w, table, outs = _take(refs[norm:], norm, rotate, d)
    dx_ref = outs[0]
    rows = dy_ref.shape[0]
    u = _unit(d)
    dw = jnp.zeros((8, u), jnp.float32)
    for a in range(g):
        lanes = slice(a * u, (a + 1) * u)
        gy = dy_ref[:, lanes].astype(jnp.float32)
        if rotate:
            gy = _turned(gy, table, d, back=True)
        if norm:
            x = x_ref[:, lanes].astype(jnp.float32)
            inv = _inv_rms(x, d, eps)
            xhat = x * inv
            # eight partial sums down the sublanes: adds, no shuffle
            dw = dw + (gy * xhat).reshape(rows // 8, 8, u).sum(0)
            gy = gy * w
            gy = inv * (gy - xhat * _head_mean(gy * xhat, d))
        dx_ref[:, lanes] = gy.astype(dx_ref.dtype)
    if norm:
        outs[1][...] = dw


def _call(kernel, name, rowwise, scale, table, d, eps, rows, lanes,
          interpret):
    """One of the two kernels over the [N, H*D] operands `rowwise` (x,
    or dy and the saved x): a result of their shape and dtype and, from
    the backward under a norm, dScale's partial sums a block."""
    norm, rotate = scale is not None, bool(table)
    n, hd = rowwise[0].shape
    u = _unit(d)
    grid = (n // rows, hd // lanes)
    block = pl.BlockSpec((rows, lanes), lambda i, j: (i, j))
    operands, specs = list(rowwise), [block] * len(rowwise)
    out_shape = [jax.ShapeDtypeStruct((n, hd), rowwise[0].dtype)]
    out_specs = [block]
    if norm:
        w = scale.astype(jnp.float32)
        # the heads of a lane tile side by side, as the table's are
        operands.append((jnp.tile(w, u // d) if u != d else w).reshape(1, u))
        specs.append(pl.BlockSpec((1, u), lambda i, j: (0, 0)))
        if kernel is _bwd_kernel:
            out_shape.append(jax.ShapeDtypeStruct(grid + (8, u), jnp.float32))
            out_specs.append(pl.BlockSpec((None, None, 8, u),
                                          lambda i, j: (i, j, 0, 0)))
    if rotate:
        # the table's blocks come round every `turns` blocks of rows
        turns = table[0].shape[0] // rows
        operands += table
        specs += [pl.BlockSpec((rows, u), lambda i, j: (i % turns, 0))] \
            * len(table)
    return pl.pallas_call(
        functools.partial(kernel, norm=norm, rotate=rotate, d=d,
                          g=lanes // u, eps=eps),
        grid=grid, in_specs=specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name=name,
    )(*operands)


# jitted as the flash kernels are: a stack of layers traces and lowers
# each kernel once
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _rotary_fwd(x, scale, table, *static):
    return _call(_fwd_kernel, "qk_norm_rope_fwd", [x], scale, table,
                 *static)[0]


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _rotary_bwd(dy, x, scale, table, d, *static):
    if scale is None:
        return _call(_bwd_kernel, "qk_norm_rope_bwd", [dy], None, table, d,
                     *static)[0], None
    dx, partial_sums = _call(_bwd_kernel, "qk_norm_rope_bwd", [dy, x], scale,
                             table, d, *static)
    dscale = partial_sums.sum((0, 1, 2))
    if dscale.shape[0] != d:        # a lane tile's heads, side by side
        dscale = dscale.reshape(-1, d).sum(0)
    return dx, dscale.astype(scale.dtype)


# x [N, H*D], scale [D] or None, `rope_table`'s tuple or (); static: d,
# eps, rows, lanes, interpret
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _norm_rope(x, scale, table, *static):
    return _rotary_fwd(x, scale, table, *static)


def _norm_rope_fwd(x, scale, table, *static):
    saved = x if scale is not None else None
    return _rotary_fwd(x, scale, table, *static), (saved, scale, table)


def _norm_rope_bwd(*args):
    *static, (x, scale, table), dy = args
    dx, dscale = _rotary_bwd(dy, x, scale, table, *static)
    return dx, dscale, tuple(jnp.zeros_like(tab) for tab in table)


_norm_rope.defvjp(_norm_rope_fwd, _norm_rope_bwd)


def _blocks(n, period, hd, d, itemsize):
    """(rows, lanes) of a grid step's block of the [n, hd] view: as many
    whole units (heads, or lane tiles of heads) as _MAX_LANES and
    _MAX_HEADS allow, and
    the largest number of rows up to _BLOCK_BYTES that divides the
    period of the positions (n where nothing rotates) in whole sublane
    tiles of x's dtype; rows 0 where there is none, or where hd is not
    whole units (an odd number of heads of 64)."""
    u = _unit(d)
    if hd % u:
        return 0, u
    lanes = u * _largest_divisor(
        hd // u, max(min(_MAX_LANES, _MAX_HEADS * d) // u, 1))
    tile = 32 // itemsize
    most = _BLOCK_BYTES // (lanes * itemsize) // tile
    rows = period or n
    if rows % tile or not most:
        return 0, lanes
    return tile * _largest_divisor(rows // tile, most), lanes


def _resolve_path(x, d, rows, rotate, force):
    """ "pallas" / "interpret" / "xla": auto takes the kernel on a TPU
    where a head is whole lane tiles or a lane tile whole heads, the
    rows cut into blocks and the jax.numpy form would need the heads'
    view or rotate-half's slices.
    ONE group over all of the last dimension, not rotated, is a plain
    row reduction that XLA fuses with its neighbours (the residual add
    before it, the projections' cast after it): in the block-diffusion
    step the kernel there was 2 ms a step slower than leaving it to XLA
    (my chip run, PR 33), though faster called alone."""
    if force is None:
        usable = (_unit(d) % _LANES == 0 and rows > 0
                  and (rotate or d != x.shape[-1]))
        return "pallas" if usable and _on_tpu(x) else "xla"
    if force != "xla" and not rows:
        raise ValueError("norm_rope: %s rows of %s cannot be cut into "
                         "blocks of whole sublane tiles" % (x.shape, x.dtype))
    return force


def norm_rope(x, scale=None, n_head=1, theta=None, wrap=0, epsilon=1e-6,
              force=None):
    """RMSNorm over each of the `n_head` heads of x [..., H*D]'s last
    dimension with ONE weight `scale` [D] (None: no norm), then the
    rotary embedding of each head, rotate-half form, by the row's
    position (`theta` None: no rotation; a sequence of D / 2 floats:
    the frequencies themselves, given): its index in x [B, T, H*D]'s
    T, taken modulo `wrap` where given. float32 inside, x's dtype out.

    force: None = auto, "pallas" / "interpret" / "xla" pin a path (tests
    run the kernel on the CPU with "interpret")."""
    norm, rotate = scale is not None, theta is not None
    hd = x.shape[-1]
    d = hd // n_head
    if hd % n_head or (norm and scale.shape != (d,)) or (rotate and d % 2) \
            or (isinstance(theta, (tuple, list)) and len(theta) != d // 2):
        raise ValueError(
            "norm_rope: x of shape %s is not %d heads of an even size "
            "under a weight of shape %s"
            % (x.shape, n_head, scale.shape if norm else None))
    if rotate and x.ndim != 3:
        raise ValueError("norm_rope: rotary positions want x [B, T, H*D], "
                         "got %s" % (x.shape,))
    n = x.size // hd
    period = _period(x.shape[1], wrap) if rotate else 0
    rows, lanes = _blocks(n, period, hd, d, x.dtype.itemsize)
    path = _resolve_path(x, d, rows, rotate, force)
    _LOWERINGS.inc(path=path, heads=str(n_head), head_dim=str(d),
                   norm=str(norm).lower(), rotate=str(rotate).lower())
    if path == "xla":
        return _xla(x, scale, n_head, theta, wrap, epsilon)
    if rotate and not isinstance(theta, (tuple, list)):
        theta = float(theta)
    table = rope_table(x.shape[1], wrap, d, theta) if rotate else ()
    return _norm_rope(x.reshape(n, hd), scale, table, d, float(epsilon),
                      rows, lanes, path == "interpret").reshape(x.shape)


# pallas imports placed at the end, as in flash_attention.py: a CPU-only
# environment that never takes the kernel path still imports this module
from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402
