"""The selective scan of a state-space (Mamba) layer, forward and
backward, as a pair of Pallas TPU kernels that walk time in chunks
(ISSUE 40), and the ops round it: the causal depthwise convolution in
front (the Program op ``ssm_conv``: on a TPU the kernel pair of
``ops/ssm_conv.py`` since ISSUE 65, the shifted slices of
``short_conv.causal_taps`` anywhere else), ``softplus`` for the step
size, the gates behind.

The recurrence, for a channel c of C and a state n of N (16), over the
T steps of one sequence, the state float32 whatever the operands are::

    H_t[c, n] = exp(dt_t[c] A[c, n]) H_{t-1}[c, n] + dt_t[c] s_t[c] B_t[n]
    y_t[c]    = sum_n H_t[c, n] C_t[n] + D[c] s_t[c]           H_0 = 0

``s`` and ``dt`` are ``[B, T, C]``, ``A`` ``[C, N]`` (negative), ``B_t``
and ``C_t`` ``[B, T, N]``, ``D`` ``[C]``. ``H`` whole would be ``[T, C,
N]`` float32 (2.7 GB a layer at T 8,192, C 5,120): neither kernel writes
it. No product of matrices computes this: the decay differs for every
(c, n), so the work is the vector unit's, about ten operations a state
update, and the kernels' roofline is the bytes of their operands.

The kernels. A grid step holds a CHUNK of ``chunk`` steps of a GROUP of
``group`` channels (a multiple of 128): grid ``(B, T / chunk, C /
group)``, all three sequential, the channel groups innermost so that
the blocks of B_t and C_t, which every group reads, are fetched once a
chunk. The state of a group is ``[N, group]``, states along the
sublanes and channels along the lanes (a step's ``dt`` and ``s`` rows
broadcast along sublanes, which is cheap; the reduction over n for
``y`` is over sublanes), held in VMEM scratch for every group across
the chunks, and inside a chunk carried in registers through a
``fori_loop`` over the steps, 128 lanes to a carried value so that the
groups' chains are independent work for the scheduler. B_t and C_t
arrive broadcast over 128 lanes, ``[B, T, N, 128]`` float32, made by
XLA before the call (67 MB each at the cell's size, read once a pass:
a lane broadcast of a ``[N]`` column inside the loop is the expensive
direction on this chip).

* ``selective_scan_fwd`` writes ``y`` and the state at each chunk's
  START, ``[B, T / chunk, N, C]`` float32 (21 MB at chunk 128).
* ``selective_scan_bwd`` walks the chunks LAST to first. In a chunk it
  first recomputes the states forward from the saved one into VMEM
  (``[chunk + 1, N, group]``), then runs the reverse recurrence ``G_t =
  dy_t C_t + a_{t+1} G_{t+1}`` with G carried like the state, and makes
  from one ``G_t``, ``H_t`` and ``H_{t-1}`` the gradients of all six
  inputs: ``ds``, ``ddt`` as rows; ``dA`` summed in a block that stays
  in VMEM for the whole grid; ``dB_t`` and ``dC_t`` as partial sums
  over the channels that share a lane, ``[B, T, N, 128]``, which XLA
  sums over the lanes; ``dD`` is a reduction of ``dy * s`` that XLA
  does.

T is padded to a multiple of the chunk and C to a multiple of the group
with zeros: a padded step has ``dt`` 0, so its decay is 1 and it adds
nothing, in either direction.

Dispatch: ``selective_scan`` takes the kernels on a TPU and the plain
``lax.scan`` form (``scan_steps``: T iterations, the tests' truth and
the CPU's path) elsewhere; ``force`` pins ``"pallas"``, ``"interpret"``
(the kernels on the CPU) or ``"steps"``. Each lowering counts itself in
``ptpu_scan_lowerings_total{path, direction, chunk, d_state}``.

Sizes (my chip runs, PR 40; ``PERF.md`` section 6 has the readings):
``_CHUNK`` and ``_GROUP`` below.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register
from ..monitor import metrics as _metrics
from . import ssm_conv
from .flash_attention import _on_tpu
from .short_conv import causal_taps

_LANES = 128
_CHUNK = 128        # steps a grid step walks
_GROUP = 1024       # channels a grid step holds: 8 independent chains
_VMEM_BYTES = 64 * 1024 * 1024

_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_scan_lowerings_total",
    "selective scan dispatches at trace time (one a lowering of a "
    "direction, none a step): the path taken (pallas: the chunked "
    "kernels; interpret: the same on the CPU; steps: lax.scan over T), "
    "the direction, the chunk's steps (0 on the steps path) and the "
    "state's size",
    ("path", "direction", "chunk", "d_state"))


# -- the plain form ---------------------------------------------------------

def scan_steps(s, dt, a, b, c, d, state_dtype=jnp.float32):
    """The recurrence as ``lax.scan`` over T: the truth the kernels are
    held to, and the path where there is no TPU. ``state_dtype`` holds
    the state between steps (float32; anything lower is a control)."""
    f32 = jnp.float32
    a, d = a.astype(f32), d.astype(f32)

    def step(h, xs):
        s_t, dt_t, b_t, c_t = (x.astype(f32) for x in xs)
        h = jnp.exp(dt_t[..., None] * a) * h.astype(f32) \
            + (dt_t * s_t)[..., None] * b_t[:, None, :]
        y = jnp.sum(h * c_t[:, None, :], -1) + d * s_t
        return h.astype(state_dtype), y

    h0 = jnp.zeros(s.shape[:1] + a.shape, state_dtype)
    _, y = lax.scan(step, h0, tuple(jnp.swapaxes(x, 0, 1)
                                    for x in (s, dt, b, c)))
    return jnp.swapaxes(y, 0, 1).astype(s.dtype)


# -- the kernels ------------------------------------------------------------

def _lane_groups(group):
    return [slice(r * _LANES, (r + 1) * _LANES)
            for r in range(group // _LANES)]


def _rows(ref, value):
    """Fill a ``[chunk / 8, 8, group]`` float32 scratch from a ``[chunk,
    group]`` block: a step's row is then ``ref[i, j:j + 1]``, a dynamic
    tile and a static sublane (a dynamic ROW of a ``[chunk, group]``
    ref is not a load the chip's compiler takes)."""
    ref[...] = value.astype(jnp.float32).reshape(ref.shape)


def _whole(ref):
    return ref[...].reshape(-1, ref.shape[-1])


def _step(h, dt_t, s_t, a, bb):
    """One step of the recurrence on a lane group: h, a, bb [N, 128];
    dt_t, s_t rows [1, 128]."""
    return jnp.exp(dt_t * a) * h + (dt_t * s_t) * bb


def _fwd_kernel(s_ref, dt_ref, a_ref, bb_ref, cb_ref, d_ref, y_ref, hs_ref,
                h_scr, s32, dt32, y32, *, chunk, group):
    t_i, c_i = pl.program_id(1), pl.program_id(2)
    lanes = _lane_groups(group)

    @pl.when(t_i == 0)
    def _():
        h_scr[c_i] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    _rows(s32, s_ref[0])
    _rows(dt32, dt_ref[0])
    a = [a_ref[:, r] for r in lanes]
    hs_ref[0, 0] = h_scr[c_i]

    def steps(i, hs):
        hs = list(hs)
        for j in range(8):
            row = slice(j, j + 1)
            bb, cb = bb_ref[0, i * 8 + j], cb_ref[0, i * 8 + j]
            for k, r in enumerate(lanes):
                hs[k] = _step(hs[k], dt32[i, row, r], s32[i, row, r], a[k],
                              bb)
                y32[i, row, r] = jnp.sum(hs[k] * cb, axis=0, keepdims=True)
        return tuple(hs)

    hs = lax.fori_loop(0, chunk // 8, steps,
                       tuple(h_scr[c_i, :, r] for r in lanes))
    for r, h in zip(lanes, hs):
        h_scr[c_i, :, r] = h
    y_ref[0] = (_whole(y32) + d_ref[...] * _whole(s32)).astype(y_ref.dtype)


def _bwd_kernel(s_ref, dt_ref, dy_ref, a_ref, bb_ref, cb_ref, d_ref, hs_ref,
                ds_ref, ddt_ref, da_ref, dbp_ref, dcp_ref,
                g_scr, h_all, s32, dt32, dy32, r1, r2, *, chunk, group):
    b_i, t_i, c_i = (pl.program_id(i) for i in range(3))
    lanes = _lane_groups(group)
    zeros = jnp.zeros(g_scr.shape[1:], jnp.float32)

    @pl.when((b_i == 0) & (t_i == 0))
    def _():
        da_ref[c_i] = zeros

    @pl.when(t_i == 0)          # the LAST chunk: the walk is reversed
    def _():
        g_scr[c_i] = zeros

    @pl.when(c_i == 0)
    def _():
        dbp_ref[...] = jnp.zeros(dbp_ref.shape, jnp.float32)
        dcp_ref[...] = jnp.zeros(dcp_ref.shape, jnp.float32)

    _rows(s32, s_ref[0])
    _rows(dt32, dt_ref[0])
    _rows(dy32, dy_ref[0])
    a = [a_ref[:, r] for r in lanes]
    h_all[0] = hs_ref[0, 0]

    def forward(i, hs):
        hs = list(hs)
        for j in range(8):
            row, t = slice(j, j + 1), i * 8 + j
            bb = bb_ref[0, t]
            for k, r in enumerate(lanes):
                hs[k] = _step(hs[k], dt32[i, row, r], s32[i, row, r], a[k],
                              bb)
                h_all[t + 1, :, r] = hs[k]
        return tuple(hs)

    lax.fori_loop(0, chunk // 8, forward,
                  tuple(hs_ref[0, 0, :, r] for r in lanes))

    def reverse(at, carry):
        i = chunk // 8 - 1 - at
        gs, das = (list(x) for x in carry)
        for j in reversed(range(8)):
            row, t = slice(j, j + 1), i * 8 + j
            bb, cb = bb_ref[0, t], cb_ref[0, t]
            dc = jnp.zeros(bb.shape, jnp.float32)
            db = jnp.zeros(bb.shape, jnp.float32)
            for k, r in enumerate(lanes):
                dt_t, dy_t = dt32[i, row, r], dy32[i, row, r]
                decay = jnp.exp(dt_t * a[k])
                g = dy_t * cb + gs[k]
                dc = dc + dy_t * h_all[t + 1, :, r]
                x = g * h_all[t, :, r] * decay
                r1[i, row, r] = jnp.sum(x * a[k], axis=0, keepdims=True)
                r2[i, row, r] = jnp.sum(g * bb, axis=0, keepdims=True)
                das[k] = das[k] + x * dt_t
                db = db + g * (dt_t * s32[i, row, r])
                gs[k] = g * decay
            dcp_ref[0, t] += dc
            dbp_ref[0, t] += db
        return tuple(gs), tuple(das)

    gs, das = lax.fori_loop(
        0, chunk // 8, reverse,
        (tuple(g_scr[c_i, :, r] for r in lanes),
         tuple(zeros[:, r] for r in lanes)))
    for r, g, da in zip(lanes, gs, das):
        g_scr[c_i, :, r] = g
        da_ref[c_i, :, r] += da
    ddt_ref[0] = (_whole(r1) + _whole(r2) * _whole(s32)).astype(
        ddt_ref.dtype)
    ds_ref[0] = (_whole(r2) * _whole(dt32)
                 + d_ref[...] * _whole(dy32)).astype(ds_ref.dtype)


def _sizes(t, c, chunk, group):
    """(chunk, group, padded T, padded C): a chunk of at most `chunk`
    steps, a multiple of 16 (a bf16 tile's rows); a group of at most
    `group` channels, a multiple of 128."""
    up = lambda n, m: -(-n // m) * m
    chunk = min(chunk, up(t, 16))
    group = min(group, up(c, _LANES))
    return chunk, group, up(t, chunk), up(c, group)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3,
                                vmem_limit_bytes=_VMEM_BYTES)


def _padded(x, tp, cp=None):
    pad = [(0, 0), (0, tp - x.shape[1]),
           (0, 0 if cp is None else cp - x.shape[2])]
    return jnp.pad(x, pad) if any(p[1] for p in pad) else x


def _operands(s, dt, a, b, c, d, chunk, group):
    """The kernels' operands: s and dt padded, A turned to ``[N, C]``,
    B_t and C_t broadcast over the lanes, D a row."""
    f32 = jnp.float32
    (bsz, t, ch), n = s.shape, a.shape[1]
    chunk, group, tp, cp = _sizes(t, ch, chunk, group)
    over_lanes = lambda x: jnp.broadcast_to(
        _padded(x.astype(f32), tp)[..., None], (bsz, tp, n, _LANES))
    wide = lambda x: jnp.pad(x.astype(f32), [(0, 0), (0, cp - ch)])
    return (chunk, group, tp, cp), (
        _padded(s, tp, cp), _padded(dt, tp, cp), wide(a.T),
        over_lanes(b), over_lanes(c), wide(d[None, :]))


def _specs(chunk, group, n, at):
    """BlockSpecs of (a [B, T, C] operand, A, B_t / C_t, D, the saved
    states), `at` mapping the grid's chunk index to the chunk walked."""
    return (pl.BlockSpec((1, chunk, group), lambda b, t, c: (b, at(t), c)),
            pl.BlockSpec((n, group), lambda b, t, c: (0, c)),
            pl.BlockSpec((1, chunk, n, _LANES),
                         lambda b, t, c: (b, at(t), 0, 0)),
            pl.BlockSpec((1, group), lambda b, t, c: (0, c)),
            pl.BlockSpec((1, 1, n, group),
                         lambda b, t, c: (b, at(t), 0, c)))


# jitted, as the flash kernels' wrappers are: a stack of layers traces
# and lowers each kernel once, and the kernels keep their own names in
# the compiled program whatever transformation traced the call
@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _fwd_pallas(s, dt, a, b, c, d, chunk, group, interpret):
    f32 = jnp.float32
    (chunk, group, tp, cp), ops = _operands(s, dt, a, b, c, d, chunk, group)
    bsz, n = s.shape[0], a.shape[1]
    grid = (bsz, tp // chunk, cp // group)
    row, a_spec, bc_spec, d_spec, hs_spec = _specs(chunk, group, n,
                                                   lambda t: t)
    y, hs = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, group=group),
        grid=grid,
        in_specs=[row, row, a_spec, bc_spec, bc_spec, d_spec],
        out_specs=[row, hs_spec],
        out_shape=[jax.ShapeDtypeStruct((bsz, tp, cp), s.dtype),
                   jax.ShapeDtypeStruct((bsz, grid[1], n, cp), f32)],
        scratch_shapes=[pltpu.VMEM((grid[2], n, group), f32)]
        + [pltpu.VMEM((chunk // 8, 8, group), f32)] * 3,
        compiler_params=_params(), interpret=interpret,
        name="selective_scan_fwd")(*ops)
    return y[:, :s.shape[1], :s.shape[2]], hs


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _bwd_pallas(s, dt, a, b, c, d, hs, dy, chunk, group, interpret):
    f32 = jnp.float32
    (chunk, group, tp, cp), ops = _operands(s, dt, a, b, c, d, chunk, group)
    sp, dtp, at_, bb, cb, dd = ops
    (bsz, t, ch), n = s.shape, a.shape[1]
    grid = (bsz, tp // chunk, cp // group)
    last = grid[1] - 1
    row, a_spec, bc_spec, d_spec, hs_spec = _specs(chunk, group, n,
                                                   lambda t: last - t)
    da_spec = pl.BlockSpec((grid[2], n, group), lambda b, t, c: (0, 0, 0))
    ds, ddt, da, dbp, dcp = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, group=group),
        grid=grid,
        in_specs=[row, row, row, a_spec, bc_spec, bc_spec, d_spec, hs_spec],
        out_specs=[row, row, da_spec, bc_spec, bc_spec],
        out_shape=[jax.ShapeDtypeStruct((bsz, tp, cp), s.dtype),
                   jax.ShapeDtypeStruct((bsz, tp, cp), dt.dtype),
                   jax.ShapeDtypeStruct((grid[2], n, group), f32),
                   jax.ShapeDtypeStruct((bsz, tp, n, _LANES), f32),
                   jax.ShapeDtypeStruct((bsz, tp, n, _LANES), f32)],
        scratch_shapes=[pltpu.VMEM((grid[2], n, group), f32),
                        pltpu.VMEM((chunk + 1, n, group), f32)]
        + [pltpu.VMEM((chunk // 8, 8, group), f32)] * 5,
        compiler_params=_params(), interpret=interpret,
        name="selective_scan_bwd")(sp, dtp, _padded(dy, tp, cp), at_, bb,
                                   cb, dd, hs)
    da = da.transpose(1, 0, 2).reshape(n, cp)[:, :ch].T
    over_lanes = lambda x: jnp.sum(x[:, :t], -1)
    dd = jnp.sum(dy.astype(f32) * s.astype(f32), (0, 1))
    return (ds[:, :t, :ch], ddt[:, :t, :ch], da.astype(a.dtype),
            over_lanes(dbp).astype(b.dtype), over_lanes(dcp).astype(c.dtype),
            dd.astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(s, dt, a, b, c, d, chunk, group, interpret):
    return _fwd_pallas(s, dt, a, b, c, d, chunk, group, interpret)[0]


def _scan_fwd(s, dt, a, b, c, d, chunk, group, interpret):
    y, hs = _fwd_pallas(s, dt, a, b, c, d, chunk, group, interpret)
    return y, (s, dt, a, b, c, d, hs)


def _scan_bwd(chunk, group, interpret, res, dy):
    path = "interpret" if interpret else "pallas"
    _LOWERINGS.inc(path=path, direction="bwd", chunk=str(chunk),
                   d_state=str(res[2].shape[1]))
    return _bwd_pallas(*res, dy, chunk, group, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(s, dt, a, b, c, d, chunk=None, group=None, force=None):
    """``y`` [B, T, C] of the recurrence in the module's docstring: s
    and dt [B, T, C], a [C, N] (negative), b and c [B, T, N], d [C].
    Differentiable in all six. ``chunk`` and ``group``: the kernels'
    steps and channels a grid step (None: ``_CHUNK``, ``_GROUP``);
    ``force``: None (the kernels on a TPU, the step loop elsewhere),
    ``"pallas"``, ``"interpret"`` or ``"steps"``."""
    path = force or ("pallas" if _on_tpu(s) else "steps")
    if path == "steps":
        _LOWERINGS.inc(path=path, direction="fwd", chunk="0",
                       d_state=str(a.shape[1]))
        return scan_steps(s, dt, a, b, c, d)
    chunk, group = chunk or _CHUNK, group or _GROUP
    if chunk % 16 or group % _LANES:
        raise ValueError(
            "selective scan: a chunk is a multiple of 16 steps and a "
            "group of 128 channels, got %r and %r" % (chunk, group))
    _LOWERINGS.inc(path=path, direction="fwd", chunk=str(chunk),
                   d_state=str(a.shape[1]))
    return _scan(s, dt, a, b, c, d, chunk, group, path == "interpret")


# -- the ops round the scan -------------------------------------------------

def causal_conv_silu(x, w, bias=None):
    """``silu(bias + sum_i w[i] * x_{t - K + 1 + i})`` over time, each
    channel by itself, zeros before the sequence: x [B, T, C], w [K, C]
    (K 4), bias [C] or None (no bias), float32 inside. On a TPU the
    kernel pair of ``ops/ssm_conv.py`` under its written backward
    (ISSUE 65; up to 9 taps, any width: a C that is not whole lane
    tiles, as Olmo-Hybrid's 1,440, ends in a block partly outside the
    array); anywhere else K shifted slices added up
    (``short_conv.causal_taps``) and autodiff's transpose of them.
    Nothing but the device and the shape chooses;
    ``ptpu_ssm_conv_lowerings_total`` says which."""
    if x.ndim == 3 and _on_tpu(x) and ssm_conv.kernel_tiles(
            x.shape[1], x.shape[2], w.shape[0]):
        return ssm_conv.conv_silu(x, w, bias)
    ssm_conv.count("taps", "fwd", w)
    f32 = jnp.float32
    return jax.nn.silu(causal_taps(
        x.astype(f32), w, None if bias is None else bias.astype(f32))
                       ).astype(x.dtype)


@register("ssm_conv")
def _ssm_conv(ctx, op):
    """X [B, T, C], Filter [K, C], Bias [C] (optional) -> Out: the
    causal depthwise convolution in front of a scan or a delta rule,
    and its SiLU."""
    ctx.set_out(op, "Out", causal_conv_silu(
        ctx.in1(op, "X"), ctx.in1(op, "Filter"), ctx.in1(op, "Bias")))


@register("ssm_dt")
def _ssm_dt(ctx, op):
    """softplus(X + Bias): the scan's step size, float32 inside."""
    x = ctx.in1(op, "X")
    out = jax.nn.softplus(x.astype(jnp.float32)
                          + ctx.in1(op, "Bias").astype(jnp.float32))
    ctx.set_out(op, "Out", out.astype(x.dtype))


@register("selective_scan")
def _selective_scan(ctx, op):
    """X and Dt [B, T, C], ALog [C, N] (A = -exp(ALog)), B and C [B, T,
    N], D [C] -> Out [B, T, C]; attrs chunk (0: the kernels' own) and
    force ("": the dispatch's own choice)."""
    a = -jnp.exp(ctx.in1(op, "ALog").astype(jnp.float32))
    ctx.set_out(op, "Out", selective_scan(
        ctx.in1(op, "X"), ctx.in1(op, "Dt"), a, ctx.in1(op, "B"),
        ctx.in1(op, "C"), ctx.in1(op, "D"),
        chunk=int(op.attr("chunk", 0)) or None,
        force=op.attr("force", "") or None))


def _gate(ctx, op):
    """X * silu(Gate), float32 inside."""
    x, gate = ctx.in1(op, "X"), ctx.in1(op, "Gate")
    out = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    ctx.set_out(op, "Out", out.astype(gate.dtype))


# one lowering under two op types, so that a device trace tells a Mamba
# mixer's output gate (y * silu(z)) from a gated memory unit's (the
# memory * silu(W_in h))
register("ssm_gate")(_gate)
register("gmu_gate")(_gate)


# pallas imports at the end, as ``flash_attention.py`` has them: a
# CPU-only environment that never takes the kernels still imports this
from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402
