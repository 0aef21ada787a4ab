"""Manifold-constrained hyper-connections (ISSUE 34): a residual stream
of n lanes that every sublayer reads through learned, input-dependent
mixes.

The stream is X [N, n*d], lane i in columns [i d, (i+1) d): whole lane
tiles where d is a multiple of 128, so a lane is a static column slice
and nothing is relaid (a [N, n, d] view would pad n to 8 sublanes).
Round a sublayer F:

    x~      = vec(X) * rsqrt(mean(vec(X)^2) + eps)          [N, n d]
    H~      = alpha * (x~ P) + bias       P [n d, n + n + n n]
    H_pre   = sigmoid(H~[:n])                                 [N, n]
    H_post  = 2 sigmoid(H~[n:2n])                             [N, n]
    H_res   = SK(exp(clamp(H~[2n:])))                         [N, n, n]
    X'      = H_res X + H_post^T F(H_pre X)

`alpha` is three scalars (pre, post, res) and SK is Sinkhorn-Knopp:
`iters` rounds of "divide each column by its sum + eps, then each row",
which takes a positive matrix onto the doubly stochastic ones (the
manifold the residual mix is held to, so that the stream's mean is kept
from layer to layer). Everything here is float32 whatever the
sublayer computes in. The coefficients are held [n, n, N], the rows
along the lanes: 20 rounds on [N, 4, 4] would run on tiles a sixteenth
full.

One Program op, `hyper_connection`, in four stages: "widen" (the
embedding copied to n lanes), "mix" (the coefficients and H_pre X),
"merge" (X') and "narrow" (the lanes summed), so that all of it is
scoped `hyper_connection.<seq>` in a trace; the Sinkhorn rounds sit
under `sinkhorn` inside it. `ptpu_hc_lowerings_total{lanes,
sinkhorn_iters}` counts the lowerings of "mix" at trace time.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import register
from ..monitor import metrics as _metrics

_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_hc_lowerings_total",
    "lowerings of a hyper-connection's coefficients at trace time (one a "
    "sublayer, none a step): the lanes of the residual stream and the "
    "Sinkhorn-Knopp rounds that make its residual mix doubly stochastic",
    ("lanes", "sinkhorn_iters"))


def sinkhorn(m, iters, eps):
    """m [n, n, ...] positive, m[i, j] row i column j: `iters` rounds
    of each column over its sum + eps, then each row."""
    with jax.named_scope("sinkhorn"):
        for _ in range(iters):
            m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
            m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def coefficients(x, proj, alpha, bias, lanes, iters, eps, clamp,
                 norm_eps=1e-6):
    """(H_pre [N, n], H_post [N, n], H_res [n, n, N]) of the stream x
    [N, n*d]; proj [n*d, n*(n+2)], its columns pre, post, res (row
    major); alpha [3]; bias [n*(n+2)]."""
    n = lanes
    _LOWERINGS.inc(lanes=str(n), sinkhorn_iters=str(iters))
    x = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + norm_eps)
    # the norm carries no weight, so it commutes with the projection:
    # one pass over x for both
    z = jnp.dot(x, proj.astype(jnp.float32),
                precision=lax.Precision.HIGHEST) * inv
    z = z * jnp.repeat(alpha.astype(jnp.float32),
                       np.array([n, n, n * n])) + bias
    pre = jax.nn.sigmoid(z[:, :n])
    post = 2.0 * jax.nn.sigmoid(z[:, n:2 * n])
    res = jnp.exp(jnp.clip(z[:, 2 * n:], *clamp)).T.reshape(n, n, -1)
    return pre, post, sinkhorn(res, iters, eps)


def _lanes(x, n):
    d = x.shape[-1] // n
    return [x[:, i * d:(i + 1) * d] for i in range(n)]


def mix_in(x, pre, lanes):
    """H_pre X: [N, d] float32."""
    xs = _lanes(x.astype(jnp.float32), lanes)
    return sum(pre[:, i:i + 1] * xs[i] for i in range(lanes))


def merge(x, post, res, y, lanes):
    """H_res X + H_post^T y: [N, n*d] float32; y [N, d]."""
    xs = _lanes(x.astype(jnp.float32), lanes)
    y = y.astype(jnp.float32)
    return jnp.concatenate(
        [sum(res[i, j][:, None] * xs[j] for j in range(lanes))
         + post[:, i:i + 1] * y for i in range(lanes)], axis=-1)


@register("hyper_connection")
def _hyper_connection(ctx, op):
    """Stages (attr `stage`) over X [B, T, .], flattened to rows:
    "widen" X [.., d] -> Out [.., n d]; "mix" X, Proj, Alpha, Bias ->
    Out = H_pre X [.., d], Post [B*T, n], Res [n, n, B*T]; "merge" X,
    Post, Res, Y -> Out [.., n d]; "narrow" X -> Out [.., d]."""
    stage, n = op.attr("stage"), int(op.attr("lanes"))
    x = ctx.in1(op, "X")
    lead = x.shape[:-1]
    rows = x.reshape(-1, x.shape[-1])
    if stage == "widen":
        out = jnp.tile(rows.astype(jnp.float32), (1, n))
    elif stage == "narrow":
        out = sum(_lanes(rows, n))
    elif stage == "mix":
        pre, post, res = coefficients(
            rows, ctx.in1(op, "Proj"), ctx.in1(op, "Alpha"),
            ctx.in1(op, "Bias"), n, int(op.attr("sinkhorn_iters")),
            float(op.attr("sinkhorn_eps")),
            (float(op.attr("clamp_min")), float(op.attr("clamp_max"))),
            float(op.attr("epsilon", 1e-6)))
        out = mix_in(rows, pre, n)
        ctx.set_out(op, "Post", post)
        ctx.set_out(op, "Res", res)
    elif stage == "merge":
        y = ctx.in1(op, "Y")
        out = merge(rows, ctx.in1(op, "Post"), ctx.in1(op, "Res"),
                    y.reshape(-1, y.shape[-1]), n)
    else:
        raise ValueError("hyper_connection: no stage %r" % (stage,))
    ctx.set_out(op, "Out", out.reshape(lead + (out.shape[-1],)))
