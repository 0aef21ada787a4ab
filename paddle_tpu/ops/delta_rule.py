"""The gated delta rule of a linear-attention layer with a MATRIX state
a head (ISSUE 53), and the small ops round it: the l2 norm of a head's
query and key, the two gates, the gated RMSNorm behind.

The recurrence, for one head over the T rows of one sequence, the state
``S`` ``[d_k, d_v]`` float32 whatever the operands are, ``S_0 = 0``::

    a_t = exp(g_t)
    S_t = a_t S_(t-1) + k_t (beta_t (v_t - a_t S_(t-1)^T k_t))^T
    o_t = S_t^T q_t

``q`` and ``k`` are ``[B, T, H, d_k]`` (normed, the query scaled, by
the caller), ``v`` ``[B, T, H, d_v]``, ``g`` (not positive) and
``beta`` ``[B, T, H]`` float32. With ``beta`` in (0, 2) the step's
transition ``a (I - beta k k^T)`` has an eigenvalue in (-1, 1).

``jax.numpy``, no kernel. Row by row the rule is T dependent rank-one
updates (``delta_rule_steps``: the tests' truth, ``force="steps"``). The
path a step takes walks CHUNKS of ``chunk`` rows (64). Inside a chunk,
with ``c_i`` the running sum of g up to row i, ``D_ij = exp(c_i - c_j)``
for ``i >= j`` and ``M = tril(diag(beta) (K K^T . D), -1)``, the rows'
updates solve a unit lower triangular system once::

    T_  = (I + M)^-1 = (I + N)(I + N^2)(I + N^4) ... , N = -M

(a strictly lower N of C rows has ``N^C = 0``, so the product of
``log2 C`` factors IS the inverse), ``W = T_ (diag(beta exp(c)) K)``,
``U = T_ (diag(beta) V)``. Across chunks a ``lax.scan`` carries the
float32 state ``[B, H, d_k, d_v]``: ``V' = U - W S``, ``S <- exp(c_C) S
+ (diag(exp(c_C - c)) K)^T V'``, and hands on each chunk's starting
state and ``V'``; the outputs are then ``(diag(exp(c)) Q) S + tril(Q
K^T . D) V'``, every chunk at once. All of it batched products over
``[B, H, T / C]``: the solve, the decays and every product with the
state or with ``T_`` in float32 at ``highest`` (a float32 product at
the default precision rounds its operands to bfloat16 on a TPU); the
two Gram products ``K K^T`` and ``Q K^T`` take the operands as they
come, bfloat16 under AMP, whose products are exact in the float32 they
are summed in. The backward is autodiff's, through the scan.

T is padded to a multiple of the chunk with zero rows (``g`` 0, ``beta``
0): a padded row decays nothing and adds nothing. Each lowering counts
itself in ``ptpu_delta_rule_lowerings_total{path, chunk, heads, d_k,
d_v}``; the device rows carry the Program op's scope
``gated_delta_rule``; a ``layers.recompute`` region may keep the op's
result under the name `DELTA_OUT` (``ops/control_flow.py``), which
spares its second forward the two output products and nothing else:
the backward reads the chunk states and ``T_``, which are made again.
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register
from ..monitor import metrics as _metrics

CHUNK = 64
_F32 = jnp.float32
_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_delta_rule_lowerings_total",
    "gated delta rule lowerings at trace time (one a lowering of the op, "
    "none a step): the path taken (chunked: the chunk walk; steps: "
    "lax.scan over T), the chunk's rows (0 on the steps path), the heads "
    "and a head's key and value widths",
    ("path", "chunk", "heads", "d_k", "d_v"))
# the name of the op's result where a recompute region keeps it
DELTA_OUT = "delta_rule_out"


# -- the plain form ---------------------------------------------------------

def delta_rule_steps(q, k, v, g, beta):
    """The recurrence a row at a time, ``lax.scan`` over T: the truth
    the chunk walk is held to. No matmul: sums of products in float32,
    the state float32 between rows."""
    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = (x.astype(_F32) for x in xs)
        s = s * jnp.exp(g_t)[..., None, None]
        seen = jnp.sum(s * k_t[..., :, None], -2)
        s = s + k_t[..., :, None] * ((v_t - seen) * b_t[..., None])[
            ..., None, :]
        return s, jnp.sum(s * q_t[..., :, None], -2)

    b, _, h, d_k = k.shape
    s0 = jnp.zeros((b, h, d_k, v.shape[-1]), _F32)
    _, o = lax.scan(step, s0, tuple(jnp.swapaxes(x, 0, 1)
                                    for x in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1).astype(v.dtype)


# -- the chunk walk ---------------------------------------------------------

def _mm(spec, a, b):
    """A batched product summed in float32: float32 operands at
    ``highest``, bfloat16 operands as they are."""
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=_F32)


def unit_lower_inverse(n):
    """``(I - n)^-1`` of a strictly lower triangular n ``[..., C, C]``:
    ``(I + n)(I + n^2)(I + n^4) ...``, exact since ``n^C = 0``."""
    c = n.shape[-1]
    inv, power, reach = jnp.eye(c, dtype=n.dtype) + n, n, 2
    while reach < c:
        power = _mm("...ij,...jk->...ik", power, power)
        inv = inv + _mm("...ij,...jk->...ik", inv, power)
        reach *= 2
    return inv


def delta_rule_chunked(q, k, v, g, beta, chunk=CHUNK):
    """The rule by chunks of `chunk` rows, as the module's docstring
    sets out."""
    b, t, h, _ = k.shape
    d_v = v.shape[-1]
    rows = -(-t // chunk) * chunk
    n = rows // chunk

    def cut(x):                 # [B, T, H, ..] -> [B, H, n, chunk, ..]
        x = jnp.pad(x, [(0, 0), (0, rows - t)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    qc, kc, vc = cut(q), cut(k), cut(v)
    gc, bc = cut(g.astype(_F32)), cut(beta.astype(_F32))
    run = jnp.cumsum(gc, -1)                            # c_i
    at = jnp.arange(chunk)
    seen = at[:, None] >= at[None, :]
    # (the difference is masked BEFORE the exp: above the diagonal it
    # is positive and may overflow)
    decay = jnp.exp(jnp.where(seen, run[..., :, None] - run[..., None, :],
                              -jnp.inf))
    m = jnp.where(at[:, None] > at[None, :], bc[..., None] * decay
                  * _mm("...ik,...jk->...ij", kc, kc), 0.0)
    inv = unit_lower_inverse(-m)
    up, k32 = jnp.exp(run)[..., None], kc.astype(_F32)
    w = _mm("...ij,...jk->...ik", inv, k32 * (bc[..., None] * up))
    u = _mm("...ij,...jk->...ik", inv, vc.astype(_F32) * bc[..., None])
    last = run[..., -1]                                 # c_C [B, H, n]
    k_out = k32 * jnp.exp(last[..., None] - run)[..., None]

    def walk(s, xs):
        w_n, u_n, k_n, a_n = xs
        fresh = u_n - _mm("bhck,bhkv->bhcv", w_n, s)
        return s * a_n[..., None, None] + _mm(
            "bhck,bhcv->bhkv", k_n, fresh), (s, fresh)

    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)
    _, (states, fresh) = lax.scan(
        walk, jnp.zeros((b, h, k.shape[-1], d_v), _F32),
        tuple(chunks_first(x) for x in (w, u, k_out, jnp.exp(last))))
    states, fresh = (jnp.moveaxis(x, 0, 2) for x in (states, fresh))
    within = jnp.where(seen, decay * _mm("...ik,...jk->...ij", qc, kc), 0.0)
    o = _mm("...ck,...kv->...cv", qc.astype(_F32) * up, states) \
        + _mm("...ij,...jv->...iv", within, fresh)
    o = jnp.moveaxis(o, 1, 3).reshape(b, rows, h, d_v)
    return o[:, :t].astype(v.dtype)


def gated_delta_rule(q, k, v, g, beta, chunk=None, force=None):
    """``o`` [B, T, H, d_v] of the recurrence in the module's docstring:
    q and k [B, T, H, d_k], v [B, T, H, d_v], g and beta [B, T, H].
    Differentiable in all five. ``chunk``: the walk's rows (None:
    ``CHUNK``); ``force``: None (the chunk walk) or ``"steps"``."""
    if q.shape != k.shape or v.shape[:3] != k.shape[:3] \
            or g.shape != k.shape[:3] or beta.shape != g.shape:
        raise ValueError(
            "gated_delta_rule: q and k [B, T, H, d_k], v [B, T, H, d_v], "
            "g and beta [B, T, H], got %s" % (
                [tuple(x.shape) for x in (q, k, v, g, beta)],))
    path = force or "chunked"
    if path not in ("chunked", "steps"):
        raise ValueError("gated_delta_rule: force is None or \"steps\", "
                         "got %r" % (force,))
    chunk = 0 if path == "steps" else int(chunk or CHUNK)
    _LOWERINGS.inc(path=path, chunk=str(chunk), heads=str(k.shape[2]),
                   d_k=str(k.shape[3]), d_v=str(v.shape[3]))
    if path == "steps":
        return delta_rule_steps(q, k, v, g, beta)
    return delta_rule_chunked(q, k, v, g, beta, chunk)


# -- the ops round the rule -------------------------------------------------

def _heads(x, n_head):
    return x.reshape(x.shape[:-1] + (n_head, x.shape[-1] // n_head))


def l2_norm_scale(x, n_head, scale=1.0, epsilon=1e-6):
    """Each head's rows of x [.., H * D] over their l2 norm, times
    `scale`: ``x / sqrt(sum(x^2) + epsilon) * scale``, float32 inside."""
    x32 = _heads(x.astype(_F32), n_head)
    out = x32 * (lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + epsilon)
                 * scale)
    return out.reshape(x.shape).astype(x.dtype)


def delta_gates(x_a, x_b, a_log, dt_bias, beta_scale=1.0):
    """``(g, beta)`` [.., H] float32: ``g = -exp(a_log) * softplus(x_a +
    dt_bias)``, the log of a row's decay; ``beta = beta_scale *
    sigmoid(x_b)``, the rule's step."""
    g = -jnp.exp(a_log.astype(_F32)) * jax.nn.softplus(
        x_a.astype(_F32) + dt_bias.astype(_F32))
    return g, beta_scale * jax.nn.sigmoid(x_b.astype(_F32))


def gated_rms_norm(x, gate, scale, epsilon=1e-6):
    """``RMSNorm(x) * silu(gate)`` over each head of x [.., H * D] under
    ONE weight `scale` [D]: the norm before the gate, float32 inside,
    the gate's dtype out."""
    x32 = _heads(x.astype(_F32), x.shape[-1] // scale.shape[0])
    normed = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                             + epsilon) * scale.astype(_F32)
    return (normed.reshape(x.shape)
            * jax.nn.silu(gate.astype(_F32))).astype(gate.dtype)


@register("gated_delta_rule")
def _gated_delta_rule(ctx, op):
    """Q and K [B, T, H * d_k], V [B, T, H * d_v], G and Beta [B, T, H]
    -> Out [B, T, H * d_v]; attrs n_head and chunk (0: the walk's
    own). The chunk walk always: the row-by-row form is the tests'."""
    h = int(op.attr("n_head"))
    v = ctx.in1(op, "V")
    out = gated_delta_rule(
        _heads(ctx.in1(op, "Q"), h), _heads(ctx.in1(op, "K"), h),
        _heads(v, h), ctx.in1(op, "G"), ctx.in1(op, "Beta"),
        chunk=int(op.attr("chunk", 0)) or None)
    ctx.set_out(op, "Out", out.reshape(v.shape))


@register("l2_norm_scale")
def _l2_norm_scale(ctx, op):
    """X [B, T, H * D] -> Out; attrs n_head, scale, epsilon."""
    ctx.set_out(op, "Out", l2_norm_scale(
        ctx.in1(op, "X"), int(op.attr("n_head")),
        float(op.attr("scale", 1.0)), float(op.attr("epsilon", 1e-6))))


@register("delta_gates")
def _delta_gates(ctx, op):
    """XA and XB [B, T, H], ALog and DtBias [H] -> G, Beta [B, T, H]
    float32; attr beta_scale."""
    g, beta = delta_gates(ctx.in1(op, "XA"), ctx.in1(op, "XB"),
                          ctx.in1(op, "ALog"), ctx.in1(op, "DtBias"),
                          float(op.attr("beta_scale", 1.0)))
    ctx.set_out(op, "G", g)
    ctx.set_out(op, "Beta", beta)


@register("gated_rms_norm")
def _gated_rms_norm(ctx, op):
    """X and Gate [B, T, H * D], Scale [D] -> Out; attr epsilon."""
    ctx.set_out(op, "Out", gated_rms_norm(
        ctx.in1(op, "X"), ctx.in1(op, "Gate"), ctx.in1(op, "Scale"),
        float(op.attr("epsilon", 1e-6))))
