"""Flash attention — Pallas TPU kernel with streaming softmax.

The fused attention kernel the registry docstring promises: computes
softmax(QK^T * scale [+ causal mask]) V without materializing the [T, T]
score matrix in HBM. Forward keeps a running (max, denominator,
accumulator) per query block while streaming key/value blocks through
VMEM; backward recomputes probabilities from the saved log-sum-exp rows
(the standard two-kernel dq / dk+dv scheme).

Reference capability: the reference's attention is composed matmul +
softmax ops (nets.py:168 scaled_dot_product_attention,
tests/unittests/transformer_model.py:41); SURVEY §7 marks attention as
the place where a hand kernel beats XLA fusion. Design follows
/opt/skills/guides/pallas_guide.md (grid + VMEM scratch carried across
the sequential k-block grid dimension; masks generated in-kernel with
broadcasted_iota).

Two sizes (PR 25). The MAJOR block is what a grid step holds in VMEM
and the DMA moves: all of T where a [T, D] operand is small there
(_auto_block: bf16 up to T 2048 at D <= 128, one grid step a head and
nothing carried between steps), else the largest divisor of T up to
1024, streamed with the running statistics in scratch. A major block
below the diagonal is one batch of work; one ON the diagonal is cut, at
trace time, into PANELS of _TILE rows whose keys stop at the diagonal,
so that tiles above it are never computed (36 of 64 tiles of 256 at
T 2048) and only the tiles it crosses are masked (_walk).

Precision: MXU operands keep the dtype they arrive in (bf16 under AMP,
float32 otherwise), p and ds are cast to it before their matmuls, and
every dot accumulates in float32; the running max, the denominator,
lse, delta, exp and all accumulators are float32. `scale` is folded
into q (into k for dk/dv) once a panel.

Shapes: q, k, v [B, H, T, D]; T must be a multiple of the block size
(the sp bucketing guarantees powers of two); D is the head dim (any
multiple of 8 — VMEM pads it to 128 lanes). The row statistics (lse,
delta) travel as [BH, 1, T]: one float a row, the rows along the
lanes, 1/128 of what a lane-broadcast [BH, T, 128] held.

Dispatch: `flash_attention(q, k, v, causal, scale)` uses the kernel on
TPU and the dense jnp math elsewhere (CPU tests exercise the kernel via
interpret mode separately).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
_LANES = 128
# None → auto block sizing in _resolve_path (_auto_block). Measured on
# one TPU v5e at the benchmark's shape, q/k/v/dy bf16 [4, 16, 2048, 64],
# causal, ms a call for flash_fwd / flash_bwd_dq / flash_bwd_dkv (my chip
# runs, PR 25; PERF.md section 6 has every row):
#   the parent, 1024^2 blocks, every score masked     0.841  1.020  1.377
#   the same with bf16 MXU operands, p / ds cast      0.840  1.051  1.329
#   the same with 512^2 blocks as grid steps          1.260  1.183  1.536
#   the same with 256^2 blocks as grid steps          2.380  2.151  3.308
#   256^2 tiles walked by lax.fori_loop in the kernel 2.550  1.394  1.848
#   this file: one block, panels of 256 cut statically 0.536  0.693  0.888
# So neither the MXU's passes nor a grid step's hand-over sets the pace:
# small tiles lose because each is a chain of matmul, reduction, exp and
# matmul that nothing overlaps, as a grid step or as a loop iteration
# alike. Straight-line panels give the scheduler the whole block at once.
# Panels of 128 to 512 rows are within 2% of one another (256: least
# masked work without the smallest matmuls); D 128 prefers the same.
DEFAULT_BLOCK_Q = None
DEFAULT_BLOCK_K = None
_AUTO_BLOCK = 1024              # streamed major block, rows
_ONE_BLOCK_BYTES = 512 * 1024   # a [T, D] operand in VMEM this small: one block
_TILE = 256                     # a panel's rows on the diagonal
_PANEL_SCORES = 1024 * 1024     # an unmasked panel's scores (4 MB in float32)


def _dense(q, k, v, causal, scale):
    return _dense_lse(q, k, v, causal, scale)[0]


def _dense_lse(q, k, v, causal, scale):
    """Dense math returning (out, lse) — lse[b,h,i] = logsumexp_j s_ij.
    The math-identical fallback for flash_attention_lse."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        t = s.shape[-1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p / l,
                     v.astype(jnp.float32)).astype(q.dtype)
    return out, (m + jnp.log(l))[..., 0]


# --------------------------------------------------------------------------
# What the three kernels share. A grid step holds one MAJOR block of q
# rows and one of keys in VMEM (what the DMA moves). Below the diagonal it
# is one batch of work; on the diagonal it is cut into panels, so that the
# tiles above the diagonal are never computed and only the tiles the
# diagonal crosses pay for a mask. The cut is static (Python, at trace
# time): a loop inside the kernel would serialise matmul, reduction and
# exp of each small tile, and measured slower than the masked work it
# saves (PERF.md section 6, PR 25).
_NT = (((1,), (1,)), ((), ()))     # a @ b^T: both contract their last dim
_NN = (((1,), (0,)), ((), ()))     # a @ b


def _dot(a, b, dims):
    """MXU operands in the dtype they arrive in, float32 accumulation."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _tile(block, target):
    """Edge of the panels a major block on the diagonal is cut into: the
    largest multiple of 128 up to `target` that divides it; a block
    with no such divisor is one panel."""
    for t in range(min(target, block) // _LANES * _LANES, 0, -_LANES):
        if block % t == 0:
            return t
    return block


def _causal(s, off, q_axis):
    """Mask one score tile. `off` = its first query row less its first
    key; queries run along `q_axis` of the tile, keys along the other."""
    qi = lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kj = lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(kj - qi <= off, s, _NEG_INF)


def _walk(panel, i, j, causal, block_q, block_k, tile, by_keys=False):
    """Run `panel(mine, segments)` over the major block (i, j).

    `mine` is a static slice of the block's query rows (of its keys if
    `by_keys`: dk/dv accumulates by key) and `segments` a list of
    (static slice of the other side, off): off None = every score of
    the segment counts, else the segment is masked with `off`, its
    first query row less its first key. A block below the diagonal is
    one unmasked segment, in panels of at most _PANEL_SCORES scores
    (a panel's scores are live in VMEM, several float32 copies of
    them); a block above it is skipped.
    Equal blocks cross the diagonal only ON it, where the cut is known
    at trace time: panel r holds rows [r t, (r+1) t) with the keys
    before them unmasked and their own keys masked (the transpose of
    that by keys). Unequal blocks cross it anywhere: one masked panel.
    """
    nq, nk = (block_k, block_q) if by_keys else (block_q, block_k)

    def whole():
        step = _tile(nq, max(_PANEL_SCORES // nk, _LANES))
        for r in range(nq // step):
            panel(slice(r * step, (r + 1) * step), [(slice(0, nk), None)])

    if not causal:
        return whole()
    first_q, first_k = i * block_q, j * block_k
    below = first_q >= first_k + block_k - 1
    pl.when(below)(whole)

    @pl.when(jnp.logical_not(below) & (first_q + block_q - 1 >= first_k))
    def _crossed():
        if block_q != block_k or tile >= block_q:
            return panel(slice(0, nq), [(slice(0, nk), first_q - first_k)])
        for r in range(block_q // tile):
            mine = slice(r * tile, (r + 1) * tile)
            rest = (slice((r + 1) * tile, block_q) if by_keys
                    else slice(0, r * tile))
            panel(mine, [(mine, 0)] + ([(rest, None)]
                                       if rest.stop > rest.start else []))


# --------------------------------------------------------------------------
# forward kernel: grid (BH, nQ, nK); scratch (m, l, acc) carried across the
# (sequential, innermost) nK dimension
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s,
                *, causal, scale, block_q, block_k, tile, nk):
    i = pl.program_id(1)   # hoisted: program_id inside a pl.when branch
    j = pl.program_id(2)   # does not interpret/lower on all paths

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def panel(rows, segments):
        # ONE streaming-softmax step of these rows over all their
        # segments: one running-max update however the keys are cut
        q = q_ref[0, rows, :] * scale               # [tq, D], once a panel
        scores = []
        for cols, off in segments:
            s = _dot(q, k_ref[0, cols, :], _NT)     # [tq, tk]
            scores.append(s if off is None else _causal(s, off, 0))
        maxes = [jnp.max(s, axis=1, keepdims=True) for s in scores]
        if nk == 1:
            # the only key block: nothing carried in, nothing to rescale
            m_new = functools.reduce(jnp.maximum, maxes)
            l = acc = 0.0
        else:
            m_prev = m_s[rows]                       # [tq, 1]
            m_new = functools.reduce(jnp.maximum, maxes, m_prev)
            alpha = jnp.exp(m_prev - m_new)
            l = alpha * l_s[rows]
            acc = alpha * acc_s[rows]
        for s, (cols, _) in zip(scores, segments):
            p = jnp.exp(s - m_new)
            v = v_ref[0, cols, :]
            l = l + jnp.sum(p, axis=1, keepdims=True)
            acc = acc + _dot(p.astype(v.dtype), v, _NN)
        m_s[rows] = m_new
        l_s[rows] = l
        acc_s[rows] = acc

    _walk(panel, i, j, causal, block_q, block_k, tile)

    @pl.when(j == nk - 1)
    def _final():
        l = jnp.maximum(l_s[:], 1e-30)
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_s[:] + jnp.log(l)).T        # [Bq, 1] -> row [1, Bq]


def _rows_spec(block, d, axis):
    """Block spec of a [BH, T, D] operand: `block` rows a grid step, the
    rows following grid axis `axis` (dk/dv's grid puts the keys first)."""
    return pl.BlockSpec((1, block, d), lambda *g: (g[0], g[axis], 0))


def _stat_spec(block, axis):
    """Block spec of a row statistic [BH, 1, T]: one float a query row,
    the rows along the lanes."""
    return pl.BlockSpec((1, 1, block), lambda *g: (g[0], 0, g[axis]))


# jitted so that a stack of layers traces and lowers each kernel ONCE:
# the panels make a kernel's body some hundreds of equations, and without
# the jit's cache every call site pays for them again (24 layers: 29 s
# more trace and lowering in the benchmark's set-up; my chip run, PR 25)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _fwd_pallas(q, k, v, causal, scale, block_q, block_k, interpret):
    b, h, t, d = q.shape
    bh = b * h
    q3 = q.reshape(bh, t, d)
    k3 = k.reshape(bh, t, d)
    v3 = v.reshape(bh, t, d)
    bq = min(block_q, t)
    bk = min(block_k, t)
    nq, nk = t // bq, t // bk
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk,
                          tile=_tile(bq, _TILE), nk=nk),
        grid=(bh, nq, nk),
        in_specs=[_rows_spec(bq, d, 1), _rows_spec(bk, d, 2),
                  _rows_spec(bk, d, 2)],
        out_specs=[_rows_spec(bq, d, 1), _stat_spec(bq, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            # one float a row, the rows along the lanes: what the
            # backward reads back as it is, and 1/128 of a lane tile a row
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return out.reshape(b, h, t, d), lse.reshape(b, h, t)


# --------------------------------------------------------------------------
# backward kernels. delta = rowsum(dy * o) is computed outside; p is
# recomputed per tile from the saved LSE. Both row statistics arrive as
# rows [1, Bq]: dk/dv, which holds the scores transposed, broadcasts them
# down the sublanes as they are; dq turns them into columns once a q block.
def _bwd_dq_kernel(q_ref, k_ref, v_ref, dy_ref, lse_ref, delta_ref, dq_ref,
                   acc_s, lse_s, delta_s,
                   *, causal, scale, block_q, block_k, tile, nk):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)
        lse_s[:] = lse_ref[0].T                      # [1, Bq] -> [Bq, 1]
        delta_s[:] = delta_ref[0].T

    def panel(rows, segments):
        q = q_ref[0, rows, :] * scale
        dy = dy_ref[0, rows, :]
        lse = lse_s[rows]
        delta = delta_s[rows]
        acc = acc_s[rows]
        for cols, off in segments:
            kk = k_ref[0, cols, :]
            s = _dot(q, kk, _NT)                     # [tq, tk]
            if off is not None:
                s = _causal(s, off, 0)
            p = jnp.exp(s - lse)
            dp = _dot(dy, v_ref[0, cols, :], _NT)
            ds = p * (dp - delta)
            acc = acc + _dot(ds.astype(kk.dtype), kk, _NN)
        acc_s[rows] = acc

    _walk(panel, i, j, causal, block_q, block_k, tile)

    @pl.when(j == nk - 1)
    def _final():
        dq_ref[0] = (acc_s[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, dy_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_s, dv_s,
                    *, causal, scale, block_q, block_k, tile, nq):
    jj = pl.program_id(1)
    i = pl.program_id(2)   # q blocks iterate innermost here

    @pl.when(i == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def panel(cols, segments):
        # the scores TRANSPOSED, [tk, tq]: k q^T contracts the last dim
        # of both, and p^T, ds^T come out as dv = p^T dy, dk = ds^T q
        # want them, with no contraction over a tile's first dim; the
        # row statistics broadcast down the sublanes as they arrive
        kk = k_ref[0, cols, :] * scale               # [tk, D], once a panel
        v = v_ref[0, cols, :]
        dk, dv = dk_s[cols], dv_s[cols]
        for rows, off in segments:
            q = q_ref[0, rows, :]
            dy = dy_ref[0, rows, :]
            st = _dot(kk, q, _NT)                    # [tk, tq]
            if off is not None:
                st = _causal(st, off, 1)
            pt = jnp.exp(st - lse_ref[0, :, rows])   # row [1, tq]
            dv = dv + _dot(pt.astype(dy.dtype), dy, _NN)
            dpt = _dot(v, dy, _NT)
            dst = pt * (dpt - delta_ref[0, :, rows])
            dk = dk + _dot(dst.astype(q.dtype), q, _NN)
        dk_s[cols] = dk
        dv_s[cols] = dv

    _walk(panel, i, jj, causal, block_q, block_k, tile, by_keys=True)

    @pl.when(i == nq - 1)
    def _final():
        dk_ref[0] = (dk_s[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _bwd_pallas(res, dy, causal, scale, block_q, block_k, interpret,
                dlse=None):
    q, k, v, o, lse = res
    b, h, t, d = q.shape
    bh = b * h
    bq = min(block_q, t)
    bk = min(block_k, t)
    # VMEM guard: the bwd kernels hold six [block, d] operands, double
    # buffered, plus float32 accumulators of the same shape; with d > 128
    # at 1024-row blocks that passes the 16 MB scoped-vmem limit. Clamp the
    # BACKWARD blocks only. The clamp must keep dividing T (a non-divisor
    # block would silently drop query rows from dq/dk/dv): shrink to the
    # largest divisor of the incoming block, which also divides T.
    if d > 128:
        bq = _largest_divisor(bq, 512)
        bk = _largest_divisor(bk, 512)
    nq, nk = t // bq, t // bk
    tile = _tile(bq, _TILE)
    delta = jnp.sum(dy.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                  # [B,H,T]
    if dlse is not None:
        # lse output cotangent: d lse_i / d s_ij = p_ij, so it folds into
        # the shared ds = p * (dp - delta') term with delta' = delta - dlse
        delta = delta - dlse.astype(jnp.float32)
    q3, k3, v3 = (a.reshape(bh, t, d) for a in (q, k, v))
    dy3 = dy.reshape(bh, t, d)
    lse3 = lse.reshape(bh, 1, t)
    delta3 = delta.reshape(bh, 1, t)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, nk=nk, tile=tile),
        grid=(bh, nq, nk),
        in_specs=[_rows_spec(bq, d, 1), _rows_spec(bk, d, 2),
                  _rows_spec(bk, d, 2), _rows_spec(bq, d, 1),
                  _stat_spec(bq, 1), _stat_spec(bq, 1)],
        out_specs=_rows_spec(bq, d, 1),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, dy3, lse3, delta3)

    # grid (bh, nK, nQ): the q-side operands follow the LAST axis here
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, nq=nq, tile=tile),
        grid=(bh, nk, nq),
        in_specs=[_rows_spec(bq, d, 2), _rows_spec(bk, d, 1),
                  _rows_spec(bk, d, 1), _rows_spec(bq, d, 2),
                  _stat_spec(bq, 2), _stat_spec(bq, 2)],
        out_specs=[_rows_spec(bk, d, 1), _rows_spec(bk, d, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, dy3, lse3, delta3)

    shape4 = (b, h, t, d)
    return dq.reshape(shape4), dk.reshape(shape4), dv.reshape(shape4)


# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    out, _ = _fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                         interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                           interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, dy):
    return _bwd_pallas(res, dy, causal, scale, block_q, block_k, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------------
# (out, lse) variant: same kernels, but the log-sum-exp rows are a public,
# differentiable output. Ring attention combines per-shard partial results
# with these (parallel/ring.py), so d(loss)/d(lse) is generally non-zero.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    return _fwd_pallas(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                           interpret)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, res, dys):
    dy, dlse = dys
    return _bwd_pallas(res, dy, causal, scale, block_q, block_k, interpret,
                       dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _on_tpu(x):
    try:
        return list(x.devices())[0].platform == "tpu"
    except Exception:
        return jax.default_backend() == "tpu"


def _largest_divisor(n, limit):
    """Largest d <= limit with n % d == 0 (block-size fitting; trace-time
    only, n is a static shape)."""
    d = min(limit, n)
    while d > 1 and n % d:
        d -= 1
    return d


def _auto_block(t, d, itemsize):
    """Major block for a sequence of t rows: all of it where a [t, d]
    operand is small in VMEM (lanes pad d to 128) and t can be cut into
    panels, so that the grid has one step a head and nothing is carried
    between steps; otherwise the largest divisor of t up to
    _AUTO_BLOCK, streamed."""
    if t % _LANES == 0 and t * max(d, _LANES) * itemsize <= _ONE_BLOCK_BYTES:
        return t
    return _largest_divisor(t, _AUTO_BLOCK)


def _resolve_path(q, scale, block_q, block_k, force):
    """Shared dispatch: (path, scale, bq, bk). path: "pallas" /
    "interpret" / "dense" — auto picks the kernel on TPU when T divides
    the blocks and the head dim tiles onto the lanes. block None → auto
    (_auto_block): all of T where that is small in VMEM, else the
    largest divisor of T up to 1024 — a divisor, so non-power-of-two T
    (1536, ...) keeps the fused kernel instead of demoting to dense."""
    scale = float(scale) if scale else q.shape[-1] ** -0.5
    t = q.shape[2]
    auto_degenerate = False
    if not block_q or not block_k:
        auto = _auto_block(t, q.shape[-1], q.dtype.itemsize)
        # a T with no divisor >= 128 below the cap (prime, 2*prime, ...)
        # would yield a near-T^2 grid of tiny blocks — far worse than
        # dense XLA; demote instead of silently compiling a cliff
        auto_degenerate = auto < min(128, t)
        block_q = block_q or auto
        block_k = block_k or auto
    path = force
    if path is None:
        usable = (t % min(block_q, t) == 0 and t % min(block_k, t) == 0
                  and t >= 128 and q.shape[-1] % 8 == 0
                  and not auto_degenerate)
        path = "pallas" if (usable and _on_tpu(q)) else "dense"
    return path, scale, min(block_q, t), min(block_k, t)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    force=None):
    """Fused multi-head attention. q/k/v: [B, H, T, D].

    force: None = auto (Pallas kernel on TPU when T divides the blocks,
    dense XLA math otherwise), "pallas" / "interpret" / "dense" pin a path
    (tests use "interpret" to run the kernel on CPU).
    """
    path, scale, bq, bk = _resolve_path(q, scale, block_q, block_k, force)
    if path == "dense":
        return _dense(q, k, v, causal, scale)
    return _flash(q, k, v, causal, scale, bq, bk, path == "interpret")


def flash_attention_lse(q, k, v, causal=False, scale=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        force=None):
    """Like flash_attention but returns (out, lse) with
    lse[b,h,i] = logsumexp_j(q_i·k_j*scale [+mask]) — the statistic ring
    attention needs to merge partial attention over K/V shards. Both
    outputs are differentiable (the lse cotangent folds into the shared
    backward kernels)."""
    path, scale, bq, bk = _resolve_path(q, scale, block_q, block_k, force)
    if path == "dense":
        return _dense_lse(q, k, v, causal, scale)
    return _flash_lse(q, k, v, causal, scale, bq, bk, path == "interpret")


# pallas imports placed at the end so a CPU-only environment that never
# takes the kernel path still imports this module (pl/pltpu are needed at
# trace time only)
from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402
