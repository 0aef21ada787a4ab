"""Flash attention — Pallas TPU kernel with streaming softmax.

The fused attention kernel the registry docstring promises: computes
softmax(QK^T * scale [+ causal mask]) V without materializing the [T, T]
score matrix in HBM. Forward keeps a running (max, denominator,
accumulator) per query block while streaming key/value blocks through
VMEM; backward recomputes per-block probabilities from the saved
log-sum-exp rows (the standard two-kernel dq / dk+dv scheme).

Reference capability: the reference's attention is composed matmul +
softmax ops (nets.py:168 scaled_dot_product_attention,
tests/unittests/transformer_model.py:41); SURVEY §7 marks attention as
the place where a hand kernel beats XLA fusion. Design follows
/opt/skills/guides/pallas_guide.md (grid + VMEM scratch carried across
the sequential k-block grid dimension; masks generated in-kernel with
broadcasted_iota).

Shapes: q, k, v [B, H, T, D]; T must be a multiple of the block size
(the sp bucketing guarantees powers of two); D is the head dim (any
multiple of 8 — lanes pad to 128 internally).

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
# None → auto block sizing in _resolve_path: large blocks win on the MXU
# (measured: 256² runs the executed matmuls at half the rate of 1024² at
# T=1024 — benchmarks/perf_probe_attn.py), while the causal block-skip
# needs nq, nk >= 2 to pay off; both push toward min(T, 1024)
DEFAULT_BLOCK_Q = None
DEFAULT_BLOCK_K = None
_AUTO_BLOCK = 1024


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
# forward kernel: grid (BH, nQ, nK); scratch (m, l, acc) carried across the
# (sequential, innermost) nK dimension
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, causal, scale, block_q, block_k, nk):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    i = pl.program_id(1)   # hoisted: program_id inside a pl.when branch
                           # does not interpret/lower on all paths

    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [Bq, D]
        kk = k_ref[0].astype(jnp.float32)           # [Bk, D]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [Bq, Bk]
        if causal:
            qi = i * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kj = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qi >= kj, s, _NEG_INF)

        m_prev = m_s[:]                              # [Bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                       # [Bq, Bk]
        l_new = alpha * l_s[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:] = m_new
        l_s[:] = l_new

    if causal:
        # causal block skip: a block whose every key index exceeds every
        # query index contributes exp(-inf)=0 — skip its matmuls (the
        # MXU time, ~half the grid for T >> block). The m/l/acc scratch
        # simply carries through.
        pl.when(i * block_q + block_q - 1 >= j * block_k)(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _final():
        l = jnp.maximum(l_s[:], 1e-30)
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_s[:] + jnp.log(l),
                                      lse_ref.shape[1:])


def _fwd_pallas(q, k, v, causal, scale, block_q, block_k, interpret):
    b, h, t, d = q.shape
    bh = b * h
    q3 = q.reshape(bh, t, d)
    k3 = k.reshape(bh, t, d)
    v3 = v.reshape(bh, t, d)
    bq = min(block_q, t)
    bk = min(block_k, t)
    nq, nk = t // bq, t // bk
    grid = (bh, nq, nk)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bhi, i, j: (bhi, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, i, j: (bhi, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, i, j: (bhi, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bhi, i, j: (bhi, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bhi, i, j: (bhi, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            # scalar-per-row stats broadcast across one 128-lane tile (the
            # TPU block layout needs the last dim to be a full lane tile)
            jax.ShapeDtypeStruct((bh, t, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return out.reshape(b, h, t, d), lse[:, :, 0].reshape(b, h, t)


# --------------------------------------------------------------------------
# backward kernels. delta = rowsum(dy * o) is computed outside; p is
# recomputed per block from the saved LSE.
def _bwd_dq_kernel(q_ref, k_ref, v_ref, dy_ref, lse_ref, delta_ref, dq_ref,
                   acc_s, *, causal, scale, block_q, block_k, nk):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)

    i = pl.program_id(1)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        kk = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qi = i * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kj = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qi >= kj, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])                   # [Bq, Bk]
        dy = dy_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(dy, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale          # [Bq, Bk]
        acc_s[:] = acc_s[:] + jax.lax.dot_general(
            ds, kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(i * block_q + block_q - 1 >= j * block_k)(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _final():
        dq_ref[0] = acc_s[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, dy_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_s, dv_s,
                    *, causal, scale, block_q, block_k, nq):
    i = pl.program_id(2)   # q blocks iterate innermost here

    @pl.when(i == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    jj = pl.program_id(1)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        kk = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qi = i * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kj = jj * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qi >= kj, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])                   # [Bq, Bk]
        dy = dy_ref[0].astype(jnp.float32)
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [Bk, D]
        dp = jax.lax.dot_general(dy, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [Bk, D]

    if causal:
        pl.when(i * block_q + block_q - 1 >= jj * block_k)(_compute)
    else:
        _compute()

    @pl.when(i == nq - 1)
    def _final():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd_pallas(res, dy, causal, scale, block_q, block_k, interpret,
                dlse=None):
    q, k, v, o, lse = res
    b, h, t, d = q.shape
    bh = b * h
    bq = min(block_q, t)
    bk = min(block_k, t)
    # VMEM guard: the bwd kernels hold s/p/dp/ds [bq, bk] f32 plus six
    # [block, d] operands; at 1024^2 blocks with d > 128 that exceeds the
    # 16 MB scoped-vmem limit (measured: d=192 needs 21.3 MB). Clamp the
    # BACKWARD blocks only — the fwd kernel carries one [bq, bk] buffer
    # and fits. The clamp must keep dividing T (a non-divisor block
    # would silently drop query rows from dq/dk/dv): shrink to the
    # largest divisor of the incoming block, which also divides T.
    if d > 128:
        bq = _largest_divisor(bq, 512)
        bk = _largest_divisor(bk, 512)
    nq, nk = t // bq, t // bk
    delta = jnp.sum(dy.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                  # [B,H,T]
    if dlse is not None:
        # lse output cotangent: d lse_i / d s_ij = p_ij, so it folds into
        # the shared ds = p * (dp - delta') term with delta' = delta - dlse
        delta = delta - dlse.astype(jnp.float32)
    q3, k3, v3 = (a.reshape(bh, t, d) for a in (q, k, v))
    dy3 = dy.reshape(bh, t, d)
    lse3 = jnp.broadcast_to(lse.reshape(bh, t)[:, :, None],
                            (bh, t, _LANES))
    delta3 = jnp.broadcast_to(delta.reshape(bh, t)[:, :, None],
                              (bh, t, _LANES))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bhi, i, j: (bhi, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, i, j: (bhi, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, i, j: (bhi, j, 0)),
            pl.BlockSpec((1, bq, d), lambda bhi, i, j: (bhi, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bhi, i, j: (bhi, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bhi, i, j: (bhi, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bhi, i, j: (bhi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, dy3, lse3, delta3)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, nq=nq),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bhi, j, i: (bhi, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, j, i: (bhi, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, j, i: (bhi, j, 0)),
            pl.BlockSpec((1, bq, d), lambda bhi, j, i: (bhi, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bhi, j, i: (bhi, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bhi, j, i: (bhi, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bhi, j, i: (bhi, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, j, i: (bhi, j, 0)),
        ],
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


def _resolve_path(q, scale, block_q, block_k, force):
    """Shared dispatch: (path, scale, bq, bk). path: "pallas" /
    "interpret" / "dense" — auto picks the kernel on TPU when T divides
    the blocks and the head dim tiles onto the lanes. block None → auto:
    the largest divisor of T up to 1024 (the measured MXU sweet spot,
    see DEFAULT_BLOCK_Q) — a divisor, so non-power-of-two T (1536, ...)
    keeps the fused kernel instead of demoting to dense."""
    scale = float(scale) if scale else q.shape[-1] ** -0.5
    t = q.shape[2]
    auto_degenerate = False
    if not block_q or not block_k:
        auto = _largest_divisor(t, _AUTO_BLOCK)
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
