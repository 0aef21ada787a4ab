"""The scan of a Mamba-2 mixer (ISSUE 62; a group of ANY number of
heads since ISSUE 64): a state-space model whose decay is ONE number a
head and step, so that its chunked form is products of matrices (the
state-space dual, arXiv:2405.21060), and the gate-then-norm that
follows it.

The recurrence, for a head h of H (in group ``g = h // (H / G)`` of G,
whose heads share ``B_t`` and ``C_t``) over the T steps of a sequence,
the state ``[P, N]`` float32 whatever the operands are, ``S_0 = 0``::

    a_t = exp(dt_t[h] A[h])
    S_t = a_t S_(t-1) + (dt_t[h] x_t[h]) B_t[g]^T
    y_t[h] = S_t C_t[g] + D[h] x_t[h]

``x`` is ``[B, T, H, P]``, ``dt`` ``[B, T, H]`` float32 (past its
softplus), ``A`` ``[H]`` (negative), ``B_t`` and ``C_t`` ``[B, T, G,
N]``, ``D`` ``[H]``. It is the decay-only form of the gated delta rule
(``ops/delta_rule.py`` with k = B_t, v = dt x, q = C_t and no delta
correction, so no triangular solve). It has a file of its own all the
same: that rule's chunk machinery is built round its solve and reads a
head's own q and k, where here ALL of a group's heads (8 of
Nemotron-3-Nano's 64, all 64 of Granite-4.0-H's) read ONE ``B_t`` and
``C_t``, whose Gram product a group computes once.

Row by row it is T rank-one updates (``ssd_steps``: the tests' truth,
``force="steps"``). The paths a step takes walk CHUNKS of L rows. With
``l_t`` the running sum of ``dt A`` inside the chunk, ``u = dt x``,
``Lam_ts = exp(l_t - l_s)`` for ``t >= s`` (0 above the diagonal),
``Gram = C B^T`` (a group's, ``[L, L]``) and ``S`` the state the chunk
starts with::

    y   = (Gram . Lam) u + diag(exp(l)) C S^T
    S' = exp(l_L) S + (diag(exp(l_L - l)) u)^T B

Two forms of the walk, one arithmetic (``_resolve_path``):

* ``ssd_chunked``, ``jax.numpy``: every chunk's parts at once as
  batched products, a ``lax.scan`` over the chunks for the state, the
  backward autodiff's. Every CPU takes it.
* the kernel pair ``ssd_scan_fwd`` / ``ssd_scan_bwd`` under ONE
  ``jax.custom_vjp``, on a TPU. A grid step walks a BLOCK of a group's
  heads, R of its ``H / G`` (``_block_heads``, from the shapes alone:
  the most that divide the group and fill 512 lanes of x, so 8 heads of
  64), J blocks to a group. Grid ``(B, G, T / L, J)``: a group's
  chunks in turn and, INSIDE a chunk, its head blocks in turn, the
  states of all its heads ``[H / G, P, N]`` float32 in a VMEM scratch
  for the whole walk (2 MB at 64 heads of 64 with 128 states). What a
  group's heads share, as far as the split leaves it shared: its
  ``B_t`` and ``C_t`` keep their block index over the J steps of a
  chunk, so they are read ONCE; the backward sums Gram's cotangent and
  the state terms of dB and dC over all J blocks in three VMEM
  scratches, the last block writing the chunk's rows of dB and dC (no
  partial sum is left to XLA). The Gram product ``C B^T`` is made by
  EVERY head block (J a chunk and group where one would do: the
  lowerings' ``grams`` label counts them): held in a VMEM scratch for
  the chunk's other blocks it cost more than made again, 1.41 ms a
  forward call against 1.23 at one group of 64 (PERF.md section 6, PR
  64). Where a group is ONE block (J = 1: 8 heads a group) the grid is
  ``(B, G, T / L)``, the backward has no scratch to sum in and the
  kernels are, op for op, what they were before ISSUE 64; a walk of
  the WHOLE group a grid step does not compile at 64
  heads a group (25.75 MB of the backward's 16 MB of scoped VMEM). A
  grid step reads the chunk's rows of its heads of x (``[L, R P]``, as
  the projection left them: no transpose), the GROUP's ``B_t`` and
  ``C_t`` (``[L, N]`` each: nothing is spread through HBM; x, ``B_t``,
  ``C_t`` and y stay FLAT, ``[B, T, H P]`` and ``[B, T, G N]``, outside
  the kernels too: a value whose last dimension is a head's 64 columns
  has half a lane tile there and costs a relayout), and the steps and
  their running sums, which XLA makes before the call (``[B, T, H]``
  float32, 2 MB) and hands over twice, as columns ``[B, H / R, T, 2
  R]`` and as rows ``[B, H / R, 2 R, T]``, so that the kernels turn
  nothing. The forward writes the chunk's rows of y and the states the
  chunk STARTED with (``[B, T / L, H, P, N]`` float32: 134 MB a layer
  at H 64, P 64, N 128, T 8,192 and chunks of 128). The backward walks
  the chunks last to first with the states' cotangent carried the same
  way; it reads the chunk's inputs, its starting states, y and dy,
  makes Gram and Lam again and writes the chunk's rows of dx, of dB
  and dC SUMMED over the group's heads, and per head two
  rows: ``<x_t, du_t>`` (dt's own cotangent) and ``<dy_t, y_t>`` (with
  the chunk's last row carrying ``<dS', S'>``), from which XLA makes
  l's cotangent, ``<dy, y> - dt <x, du>``: what the three decays'
  derivatives sum to. Products take the operands as they come
  (bfloat16 under AMP) and sum in float32; a state enters a product
  rounded to the operands' dtype and is carried and saved float32.

T is padded to whole chunks with zero rows (``dt`` 0: a padded row
decays nothing and adds nothing). ``D x`` is added by XLA outside the
kernels, which fuses it into what reads y. Each lowering counts itself
in ``ptpu_ssd_lowerings_total{path, direction, chunk, d_state,
group_heads, step_heads, grams}``; the device rows carry the Program
op's scope ``ssd_scan``.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register
from ..monitor import metrics as _metrics
# (the products: float32 operands at `highest`, bfloat16 as they come,
# summed in float32; `_dot` inside a kernel, `_mm` batched outside)
from .delta_rule import _NN, _NT, _TN, _dot, _mm
from .flash_attention import _on_tpu

# rows of a chunk, on both walks: the MXU's tile on the chip this was
# measured on. The source's `chunk_size` names its own kernel's walk;
# no result depends on either.
CHUNK = 128
# the lanes of x that a grid step walks at most (`_block_heads`): 8
# heads of 64, the block the kernels were measured at (PERF.md section
# 6, PRs 62 and 64)
_BLOCK_LANES = 512
_F32 = jnp.float32
_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_ssd_lowerings_total",
    "state-space-dual scan lowerings at trace time (one a lowering of a "
    "direction, none a step): the path taken (pallas: the kernel pair "
    "ssd_scan_fwd / ssd_scan_bwd; interpret: the same on the CPU, tests "
    "only; chunked: the jax.numpy chunk walk, whose backward is "
    "autodiff's; steps: lax.scan over T, tests only), the direction (fwd, "
    "bwd: the kernels count each; the other paths count fwd), the chunk's "
    "rows (0 on the steps path), the state's size, the heads a group "
    "has, the heads of it a grid step of the kernels walks (the other "
    "paths take a group whole) and the Gram products C B^T a group's "
    "chunk takes (0 on the steps path, which has none)",
    ("path", "direction", "chunk", "d_state", "group_heads", "step_heads",
     "grams"))


# -- the plain form ---------------------------------------------------------

def ssd_steps(x, dt, a, b, c, d):
    """The recurrence a row at a time, ``lax.scan`` over T: the truth
    the chunk walks are held to. No matmul: sums of products in
    float32."""
    per_group = x.shape[2] // b.shape[2]
    a, d = a.astype(_F32), d.astype(_F32)

    def step(s, xs):
        x_t, dt_t, b_t, c_t = (v.astype(_F32) for v in xs)
        b_t, c_t = (jnp.repeat(v, per_group, 1) for v in (b_t, c_t))
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.sum(s * c_t[:, :, None, :], -1) + d[:, None] * x_t

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], _F32)
    _, y = lax.scan(step, s0, tuple(jnp.swapaxes(v, 0, 1)
                                    for v in (x, dt, b, c)))
    return jnp.swapaxes(y, 0, 1).astype(x.dtype)


# -- the chunk walk ---------------------------------------------------------

def _running(dt, a, chunk):
    """``l`` [B, T, H] float32: the running sum of ``dt A`` inside each
    chunk of `chunk` rows (T whole chunks)."""
    b, t, h = dt.shape
    return jnp.cumsum((dt * a).reshape(b, t // chunk, chunk, h),
                      2).reshape(b, t, h)


def ssd_chunked(x, dt, a, b, c, chunk=CHUNK):
    """The scan WITHOUT ``D x`` by chunks of `chunk` rows (T whole
    chunks), as the module's docstring sets out; float32 out."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r, k = h // g, t // chunk
    run = _running(dt, a, chunk).reshape(bsz, k, chunk, g, r)
    xs = x.reshape(bsz, k, chunk, g, r, p)
    bs, cs = (v.reshape(bsz, k, chunk, g, n) for v in (b, c))
    u32 = xs.astype(_F32) * dt.reshape(run.shape)[..., None]
    u = u32.astype(x.dtype)
    at = jnp.arange(chunk)
    seen = (at[:, None] >= at[None, :])[:, :, None, None]
    # (masked BEFORE the exp: above the diagonal the difference is
    # positive and may overflow)
    decay = jnp.exp(jnp.where(seen, run[:, :, :, None] - run[:, :, None],
                              -jnp.inf))            # [B, k, t, s, G, R]
    gram = _mm("bktgn,bksgn->bktsg", cs, bs)
    within = (gram[..., None] * decay).astype(x.dtype)
    last = run[:, :, -1]                                # [B, k, G, R]
    out = (u32 * jnp.exp(last[:, :, None] - run)[..., None]).astype(x.dtype)
    add = _mm("bksgrp,bksgn->bkgrpn", out, bs)

    def walk(s, xs):
        a_k, add_k = xs
        return s * a_k[..., None, None] + add_k, s

    _, states = lax.scan(
        walk, jnp.zeros((bsz, g, r, p, n), _F32),
        (jnp.moveaxis(jnp.exp(last), 1, 0), jnp.moveaxis(add, 1, 0)))
    states = jnp.moveaxis(states, 0, 1).astype(x.dtype)
    y = _mm("bktsgr,bksgrp->bktgrp", within, u) \
        + _mm("bktgn,bkgrpn->bktgrp", cs, states) * jnp.exp(run)[..., None]
    return y.reshape(x.shape)


# -- the kernels ------------------------------------------------------------

def _total(v):
    """[1, 1]: the sum of a 2-D value."""
    return jnp.sum(jnp.sum(v, 1, keepdims=True), 0, keepdims=True)


def _seen(size):
    """[L, L]: row t sees column s where ``t >= s``."""
    return lax.broadcasted_iota(jnp.int32, (size, size), 0) \
        >= lax.broadcasted_iota(jnp.int32, (size, size), 1)


def _head_parts(r, heads, cols, rows, seen):
    """Of head r of a block of `heads`, from the chunk's columns ``[L,
    2 R]`` (dt, then l) and rows ``[2 R, L]`` (l, then l_L in every
    lane): dt [L, 1], exp(l) [L, 1], exp(l_L - l) [L, 1], exp(l_L) [1,
    1] and ``Lam`` [L, L] float32 (0 above the diagonal)."""
    dt = cols[:, r:r + 1]
    run = cols[:, heads + r:heads + r + 1]
    # l_L [1, 1] by a reduction over a row that holds it in every lane:
    # a reduction's result spreads over a tile, one lane cut out of a
    # row does not
    last = jnp.max(rows[heads + r:heads + r + 1, :], 1, keepdims=True)
    decay = jnp.exp(jnp.where(seen, run - rows[r:r + 1, :], -jnp.inf))
    return dt, jnp.exp(run), jnp.exp(last - run), jnp.exp(last), decay


def _block_heads(per_group, p):
    """The heads of a group that a grid step walks, from the shapes
    alone: the most that divide the group's `per_group` and whose
    columns of x fill `_BLOCK_LANES` lanes at most. 8 heads of 64, the
    whole group, where a group has 8; 8 of a group of 64."""
    return max(r for r in range(1, per_group + 1)
               if per_group % r == 0 and (r == 1 or r * p <= _BLOCK_LANES))


def _head_block(s_scr, heads):
    """The first of the group's heads that a grid step walks, as an
    index into `s_scr` [H / G, P, N]: `heads` times the fourth grid
    index; the number 0 where the block is the group: the grid then has
    no fourth axis and the backward no scratch to sum in, and the
    kernels are what they were before ISSUE 64."""
    if s_scr.shape[0] == heads:
        return 0
    return pl.program_id(3) * heads


def _fwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, y_ref, states_ref,
                s_scr):
    """Chunk ``program_id(2)`` of head block ``program_id(3)``;
    ``s_scr`` [H / G, P, N] carries the states of ALL the group's heads
    from chunk to chunk."""
    p = s_scr.shape[1]
    heads = x_ref.shape[2] // p
    first = _head_block(s_scr, heads)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[pl.ds(first, heads)] = jnp.zeros((heads,) + s_scr.shape[1:],
                                               s_scr.dtype)

    b, c, cols, rows = b_ref[0], c_ref[0], cols_ref[0, 0], rows_ref[0, 0]
    gram, seen = _dot(c, b, _NT), _seen(b.shape[0])
    for r in range(heads):
        at = slice(r * p, (r + 1) * p)
        dt, up, out, a_last, lam = _head_parts(r, heads, cols, rows, seen)
        s = s_scr[first + r]
        states_ref[0, 0, r] = s
        u32 = x_ref[0, :, at].astype(_F32) * dt
        u = u32.astype(b.dtype)
        y_ref[0, :, at] = (
            _dot((gram * lam).astype(b.dtype), u, _NN)
            + up * _dot(c, s.astype(b.dtype), _NT)).astype(y_ref.dtype)
        s_scr[first + r] = a_last * s + _dot((u32 * out).astype(b.dtype), b,
                                             _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, states_ref, y_ref,
                dy_ref, dx_ref, db_ref, dc_ref, drows_ref, ds_scr, du_scr,
                *sums):
    """Last chunk first; ``ds_scr`` [H / G, P, N] carries the cotangent
    of the states a chunk hands on, ``du_scr`` holds the head block's du
    for the rows. Where a group has several head blocks, Gram's
    cotangent and the state terms of dB and dC are summed over ALL of
    them in VMEM (`sums`: three float32 scratches), and the group's last
    head block writes the chunk's rows of dB and dC from the sums."""
    p = ds_scr.shape[1]
    heads = x_ref.shape[2] // p
    first = _head_block(ds_scr, heads)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[pl.ds(first, heads)] = jnp.zeros(
            (heads,) + ds_scr.shape[1:], ds_scr.dtype)

    b, c, cols, rows = b_ref[0], c_ref[0], cols_ref[0, 0], rows_ref[0, 0]
    size, dtype = b.shape[0], b.dtype
    gram, seen = _dot(c, b, _NT), _seen(size)
    dgram = jnp.zeros_like(gram)
    db, dc = jnp.zeros(b.shape, _F32), jnp.zeros(c.shape, _F32)
    head = lax.broadcasted_iota(jnp.int32, (heads, size), 0)
    lane = lax.broadcasted_iota(jnp.int32, (heads, size), 1)
    ends = jnp.zeros((heads, size), _F32)
    for r in range(heads):
        at = slice(r * p, (r + 1) * p)
        dt, up, out, a_last, lam = _head_parts(r, heads, cols, rows, seen)
        s, ds = states_ref[0, 0, r], ds_scr[first + r]
        dy = dy_ref[0, :, at]
        u32 = x_ref[0, :, at].astype(_F32) * dt
        u = u32.astype(dtype)
        # y = within u + up (c s^T); s' = a_last s + (out u)^T b
        reach = _dot(b, ds.astype(dtype), _NT)              # b ds^T [L, P]
        du = _dot((gram * lam).astype(dtype), dy, _TN) + out * reach
        dgram = dgram + _dot(dy, u, _NT) * lam
        lifted = (dy.astype(_F32) * up).astype(dtype)
        sent = (u32 * out).astype(dtype)
        dc = dc + _dot(lifted, s.astype(dtype), _NN)
        db = db + _dot(sent, ds.astype(dtype), _NN)
        # <dS', S'> = a_last <dS', S> + <out u, b dS'^T>: l_L's share,
        # at the chunk's last row
        end = a_last * _total(ds * s) + _total(u32 * out * reach)
        ends = ends + jnp.where((head == r) & (lane == size - 1), end, 0.0)
        ds_scr[first + r] = a_last * ds + _dot(lifted, c, _TN)
        dx_ref[0, :, at] = (du * dt).astype(dx_ref.dtype)
        du_scr[:, at] = du

    def write(dgram, db, dc):
        dgram = dgram.astype(dtype)
        dc_ref[0] = (dc + _dot(dgram, b, _NN)).astype(dc_ref.dtype)
        db_ref[0] = (db + _dot(dgram, c, _TN)).astype(db_ref.dtype)

    if not sums:
        write(dgram, db, dc)
    else:
        @pl.when(first == 0)
        def _():
            for scr, part in zip(sums, (dgram, db, dc)):
                scr[...] = part

        @pl.when(first > 0)
        def _():
            for scr, part in zip(sums, (dgram, db, dc)):
                scr[...] += part

        @pl.when(first == ds_scr.shape[0] - heads)
        def _():
            write(*(scr[...] for scr in sums))

    # each head's sums over its own P lanes, as ROWS: a product with
    # the heads' indicator, exact at `highest`
    width = heads * p
    mine = (lax.broadcasted_iota(jnp.int32, (heads, width), 1) // p
            == lax.broadcasted_iota(jnp.int32, (heads, width), 0)
            ).astype(_F32)
    x32 = x_ref[0].astype(_F32)
    drows_ref[0, 0, 0:heads, :] = _dot(mine, x32 * du_scr[...], _NT)
    drows_ref[0, 0, heads:2 * heads, :] = ends + _dot(
        mine, dy_ref[0].astype(_F32) * y_ref[0].astype(_F32), _NT)


def _operands(dt, run, blocks, chunk):
    """The steps dt and their running sums l, [B, T, H] float32, by
    head block (`blocks` of them, ``R = H / blocks`` heads each), as the
    kernels read them: columns ``[B, blocks, T, 2 R]`` (dt, l) and rows
    ``[B, blocks, 2 R, T]`` (l, each chunk's l_L in every lane)."""
    bsz, t, h = dt.shape
    by_block = lambda v: jnp.moveaxis(
        v.reshape(bsz, t, blocks, h // blocks), 2, 1)
    last = jnp.broadcast_to(
        run.reshape(bsz, t // chunk, chunk, h)[:, :, -1:],
        (bsz, t // chunk, chunk, h)).reshape(bsz, t, h)
    cols = jnp.concatenate([by_block(dt), by_block(run)], -1)
    rows = jnp.swapaxes(jnp.concatenate(
        [by_block(run), by_block(last)], -1), 2, 3)
    return cols, rows


def _walk(bsz, g, k, h, p, n, chunk, at):
    """The walk of `h` heads of `p` in `g` groups over `k` chunks: (the
    heads a grid step walks, the grid, the BlockSpecs of (x, b or c,
    the columns, the rows in or out, the saved states), the compiler's
    parameters). The grid is (batch, group, chunk) and, where a group
    has several head blocks, the head block INSIDE the chunk: a group's
    b and c then keep their block over its head blocks, so they are
    read once a chunk, and written once where they are results. `at`
    maps the grid's chunk index to the chunk walked."""
    r = _block_heads(h // g, p)
    blocks = h // g // r
    several = blocks > 1
    mine = lambda g, j: g * blocks + j[0] if j else g
    specs = (
        pl.BlockSpec((1, chunk, r * p),
                     lambda i, g, t, *j: (i, at(t), mine(g, j))),
        pl.BlockSpec((1, chunk, n), lambda i, g, t, *j: (i, at(t), g)),
        pl.BlockSpec((1, 1, chunk, 2 * r),
                     lambda i, g, t, *j: (i, mine(g, j), at(t), 0)),
        pl.BlockSpec((1, 1, 2 * r, chunk),
                     lambda i, g, t, *j: (i, mine(g, j), 0, at(t))),
        pl.BlockSpec((1, 1, r, p, n),
                     lambda i, g, t, *j: (i, at(t), mine(g, j), 0, 0)))
    return r, (bsz, g, k) + (blocks,) * several, specs, pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
        + ("arbitrary",) * several)


# jitted, as the flash kernels' wrappers are: a stack of layers traces
# and lowers each kernel once, and the kernels keep their own names in
# the compiled program whatever transformation traced the call. The
# wrappers take and hand on x, b and c FLAT, ``[B, T, H P]`` and ``[B,
# T, G N]``, as the projections leave them: a ``[.., H, 64]`` value has
# half a lane tile in its last dimension and costs a relayout to make.
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _fwd_pallas(x, dt, run, b, c, h, g, chunk, interpret):
    """(y [B, T, H P] without ``D x``, the chunks' starting states [B,
    T / L, H, P, N] float32)."""
    bsz, t, width = x.shape
    p, n, k = width // h, b.shape[-1] // g, t // chunk
    r, grid, (xs, bc, cols, rows, states), params = _walk(
        bsz, g, k, h, p, n, chunk, lambda t: t)
    return pl.pallas_call(
        _fwd_kernel, grid=grid,
        in_specs=[xs, bc, bc, cols, rows], out_specs=[xs, states],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, k, h, p, n), _F32)],
        scratch_shapes=[pltpu.VMEM((h // g, p, n), _F32)],
        compiler_params=params, interpret=interpret,
        name="ssd_scan_fwd")(x, b, c, *_operands(dt, run, h // r, chunk))


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _bwd_pallas(x, dt, run, b, c, states, y, dy, h, g, chunk, interpret):
    """The cotangents of (x, dt, l, b, c)."""
    bsz, t, width = x.shape
    p, n, k = width // h, b.shape[-1] // g, t // chunk
    r, grid, (xs, bc, cols, rows, s_spec), params = _walk(
        bsz, g, k, h, p, n, chunk, lambda t: k - 1 - t)
    blocks = h // g // r
    dx, db, dc, rows_out = pl.pallas_call(
        _bwd_kernel, grid=grid,
        in_specs=[xs, bc, bc, cols, rows, s_spec, xs, xs],
        out_specs=[xs, bc, bc, rows],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct((bsz, h // r, 2 * r, t), _F32)],
        scratch_shapes=[pltpu.VMEM((h // g, p, n), _F32),
                        pltpu.VMEM((chunk, r * p), _F32)]
        # the sums of Gram's cotangent and of dB's and dC's state terms
        + [pltpu.VMEM((chunk, chunk), _F32)] * (blocks > 1)
        + [pltpu.VMEM((chunk, n), _F32)] * (2 * (blocks > 1)),
        compiler_params=params, interpret=interpret,
        name="ssd_scan_bwd")(x, b, c, *_operands(dt, run, h // r, chunk),
                             states, y, dy)
    # [B, H / R, 2 R, T] -> two of [B, T, H]: <x, du>, which is dt's own
    # cotangent, and <dy, y> with the chunks' ends
    per_head = jnp.moveaxis(rows_out.reshape(bsz, h // r, 2, r, t), (2, 4),
                            (0, 2)).reshape(2, bsz, t, h)
    return dx, per_head[0], per_head[1] - dt * per_head[0], db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _scan(x, dt, run, b, c, h, g, chunk, interpret):
    return _fwd_pallas(x, dt, run, b, c, h, g, chunk, interpret)[0]


def _scan_fwd(x, dt, run, b, c, h, g, chunk, interpret):
    y, states = _fwd_pallas(x, dt, run, b, c, h, g, chunk, interpret)
    return y, (x, dt, run, b, c, states, y)


def _count(path, direction, chunk, n, per_group, step_heads):
    """One lowering: the path and direction, the chunk's rows, the
    state's size, the heads a group has, the heads a grid step walks
    and the Gram products a group's chunk takes: one a grid step, so
    the head blocks to a group (each makes its own: read from a VMEM
    scratch the product cost more than made again, PERF.md section 6,
    PR 64); none row by row."""
    _LOWERINGS.inc(path=path, direction=direction, chunk=str(chunk),
                   d_state=str(n), group_heads=str(per_group),
                   step_heads=str(step_heads),
                   grams=str(per_group // step_heads if chunk else 0))


def _scan_bwd(h, g, chunk, interpret, res, dy):
    x, b = res[0], res[3]
    _count("interpret" if interpret else "pallas", "bwd", chunk,
           b.shape[-1] // g, h // g, _block_heads(h // g, x.shape[-1] // h))
    return _bwd_pallas(*res, dy, h, g, chunk, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _resolve_path(on_tpu, force=None):
    """ "pallas" / "interpret" / "chunked" / "steps": with no `force`,
    the kernels on a TPU, the jax.numpy chunk walk anywhere else."""
    if force is None:
        return "pallas" if on_tpu else "chunked"
    if force not in ("pallas", "interpret", "chunked", "steps"):
        raise ValueError(
            "ssd_scan: force is None, \"pallas\", \"interpret\", "
            "\"chunked\" or \"steps\", got %r" % (force,))
    return force


def ssd_scan(x, dt, a, b, c, d, chunk=None, force=None):
    """``y`` [B, T, H, P] of the recurrence in the module's docstring:
    x [B, T, H, P], dt [B, T, H], a and d [H], b and c [B, T, G, N], H
    a multiple of G. Differentiable in all six. ``chunk``: the walk's
    rows (None: `CHUNK`); ``force``, for tests and probes: None
    (`_resolve_path`), ``"pallas"``, ``"interpret"`` (the kernels on
    the CPU), ``"chunked"`` or ``"steps"``. (`ssd_scan_flat` with the
    heads and groups told apart: a Program's op calls that one.)"""
    if x.ndim != 4 or b.ndim != 4 or b.shape != c.shape:
        raise ValueError(
            "ssd_scan: x [B, T, H, P], dt [B, T, H], a and d [H], b and c "
            "[B, T, G, N] with H a multiple of G, got %s" % (
                [tuple(v.shape) for v in (x, dt, a, b, c, d)],))
    flat = lambda v: v.reshape(v.shape[:2] + (-1,))
    return ssd_scan_flat(flat(x), dt, a, flat(b), flat(c), d, x.shape[2],
                         b.shape[2], chunk, force).reshape(x.shape)


def ssd_scan_flat(x, dt, a, b, c, d, n_head, n_group, chunk=None,
                  force=None):
    """`ssd_scan` on x ``[B, T, H P]`` and b, c ``[B, T, G N]`` as a
    projection leaves them, `n_head` H and `n_group` G told; ``[B, T, H
    P]`` out. No value with a head's 64 columns as its last dimension
    is made on the kernels' path."""
    h, g = int(n_head), int(n_group)
    if x.ndim != 3 or dt.shape != x.shape[:2] + (h,) or b.shape != c.shape \
            or b.shape[:2] != x.shape[:2] or h % g or x.shape[2] % h \
            or b.shape[2] % g or a.shape != (h,) or d.shape != (h,):
        raise ValueError(
            "ssd_scan: x [B, T, H, P], dt [B, T, H], a and d [H], b and c "
            "[B, T, G, N] with H a multiple of G, got %s at H %d, G %d" % (
                [tuple(v.shape) for v in (x, dt, a, b, c, d)], h, g))
    p, n = x.shape[2] // h, b.shape[2] // g
    path = _resolve_path(_on_tpu(x), force)
    heads = lambda v, k: v.reshape(v.shape[:2] + (k, v.shape[2] // k))
    if path == "steps":
        _count(path, "fwd", 0, n, h // g, 0)
        return ssd_steps(heads(x, h), dt, a, heads(b, g), heads(c, g),
                         d).reshape(x.shape)
    chunk = int(chunk or CHUNK)
    if path == "pallas" and chunk % 128:
        raise ValueError("ssd_scan: the kernels' chunk is whole lane tiles "
                         "(a multiple of 128 rows), got %d" % chunk)
    t = x.shape[1]
    rows = -(-t // chunk) * chunk
    pad = lambda v: v if rows == t else jnp.pad(
        v, [(0, 0), (0, rows - t), (0, 0)])
    xp, bp, cp = pad(x), pad(b), pad(c)
    dtp, a32 = pad(dt.astype(_F32)), a.astype(_F32)
    kernels = path in ("pallas", "interpret")
    _count(path, "fwd", chunk, n, h // g,
           _block_heads(h // g, p) if kernels else h // g)
    if kernels:
        y = _scan(xp, dtp, _running(dtp, a32, chunk), bp, cp, h, g, chunk,
                  path == "interpret")
    else:
        y = ssd_chunked(heads(xp, h), dtp, a32, heads(bp, g), heads(cp, g),
                        chunk).reshape(xp.shape)
    y = y[:, :t].astype(_F32) + jnp.repeat(d.astype(_F32), p) \
        * x.astype(_F32)
    return y.astype(x.dtype)


def saved_states_bytes(elements, d_state, chunk=None):
    """The bytes of the chunks' starting states that the forward kernel
    saves for the backward, beside a result of `elements` values (rows
    x H x P): float32 ``[rows / L, H, P, N]``. What a recompute
    region's plan adds to the op's own result
    (``control_flow._plan_kept``)."""
    return 4 * elements * d_state // int(chunk or CHUNK)


# -- the gate and the norm behind the scan ----------------------------------

def gated_group_norm(x, gate, scale, groups, epsilon=1e-5):
    """``RMSNorm(x * silu(gate)) * scale``, the gate BEFORE the norm,
    the norm over each of `groups` equal groups of the channels of x
    [.., C] by itself, `scale` [C]; float32 inside, x's dtype out.
    (``delta_rule.gated_rms_norm`` norms first, over a head, under one
    weight a head: another op.)"""
    gated = x.astype(_F32) * jax.nn.silu(gate.astype(_F32))
    # a group at a time, as lane slices side by side: ``[.., groups,
    # C / groups]`` is another tiling of the same values on a TPU (its
    # tiles hold 8 groups of one row, not 8 rows), which a reshape pays
    # for with a pass of its own
    width = x.shape[-1] // groups
    normed = []
    for at in range(0, x.shape[-1], width):
        part = gated[..., at:at + width]
        normed.append(part * lax.rsqrt(
            jnp.mean(part * part, -1, keepdims=True) + epsilon))
    return (jnp.concatenate(normed, -1) * scale.astype(_F32)).astype(x.dtype)


@register("ssd_scan")
def _ssd_scan(ctx, op):
    """X [B, T, H * P], Dt [B, T, H] (past its softplus), ALog and D
    [H], B and C [B, T, G * N] -> Out [B, T, H * P]; attrs n_head,
    n_group and chunk (0: `CHUNK`). ``A = -exp(ALog)``, float32."""
    ctx.set_out(op, "Out", ssd_scan_flat(
        ctx.in1(op, "X"), ctx.in1(op, "Dt"),
        -jnp.exp(ctx.in1(op, "ALog").astype(_F32)), ctx.in1(op, "B"),
        ctx.in1(op, "C"), ctx.in1(op, "D"), op.attr("n_head"),
        op.attr("n_group"), chunk=int(op.attr("chunk", 0)) or None))


@register("gated_group_norm")
def _gated_group_norm(ctx, op):
    """X and Gate [B, T, C], Scale [C] -> Out; attrs groups, epsilon."""
    ctx.set_out(op, "Out", gated_group_norm(
        ctx.in1(op, "X"), ctx.in1(op, "Gate"), ctx.in1(op, "Scale"),
        int(op.attr("groups")), float(op.attr("epsilon", 1e-5))))


# pallas imports at the end, as ``flash_attention.py`` has them: a
# CPU-only environment that never takes the kernels still imports this
from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402
