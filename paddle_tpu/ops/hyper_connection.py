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
under `sinkhorn` inside it.

"mix" and "merge" are bandwidth work on ONE array, the stream: [4096,
4 x 3584] float32 is 234.9 MB, 0.287 ms a pass at a v5e's 819 GB/s,
and the jax.numpy form below, each stage alone on the chip, makes 3.7 +
5.1 passes a sublayer forward and 6.9 + 9.4 backward (1.07, 1.45, 1.99
and 2.69 ms; PERF.md section 6, PR 43); in a step XLA fuses part of
that into the matmuls and norms round it, where it costs as much.
Where the rule allows, the two stages are four Pallas kernels under one
`jax.custom_vjp` each. A grid step holds WHOLE rows of the stream and
walks them in chunks of 512 lanes:

  hc_mix_fwd    one read of X: sum(x^2), the projection x P, its norm,
                H_pre = sigmoid(.) and h = sum_i pre_i X_i in the one
                visit. Out: h [N, d] and zs [N, 128], the normed
                projection in its first n (n + 2) columns and
                rsqrt(mean(x^2) + eps) in the next. H_post, the clamp,
                exp and the Sinkhorn rounds stay jax.numpy on zs, scoped
                `sinkhorn` as ever, and autodiff differentiates them.
  hc_merge_fwd  one read of X (and y in the dtype it comes in), one
                write: X'_i = sum_j res[i, j] X_j + post_i y.
  hc_merge_bwd  reads dX' and X, writes dX_a = H_res^T dX' in place of
                dX'; dy, and the n n + n row sums dX'_i . X_j and
                dX'_i . y as [N, 128].
  hc_mix_bwd    reads X and dX_a, writes dX in place of dX_a:
                dX = dX_a + pre_i dh + inv (g P^T) - x inv^2 / (n d)
                sum_c g_c zs_c, with g the cotangent of zs (H_pre's part
                of it made here from dh . X_i), and dP = X^T (g inv)
                summed across the row blocks in a resident block.

"mix" hands the stream through as one more result (x itself, no copy)
and "merge" reads that: the cotangent of the stream through "merge"
then arrives in "mix"'s backward rule and is added in `hc_mix_bwd`'s one
visit, where two uses of one array would be summed by XLA in three
passes more. So a sublayer is 1.25 + 2.125 passes forward and 3.25 +
3.25 backward (0.43, 0.76, 1.08 and 1.08 ms: 620 to 710 GB/s, where
XLA's own copy of the stream reaches 652), and each of the four kernels
shortened the step of `xing4_train_T4k` by 14 to 20 ms when the other
three were kept (PERF.md section 6): all four stay.

The same arithmetic: the stream, the coefficients and every sum are
float32. The projection keeps all six bfloat16 products of
`Precision.HIGHEST` (hi hi, hi mid, mid hi, mid mid, hi lo, lo hi of the
three-way splits of x and P); [P_hi | P_mid | P_lo] is 3 n (n + 2) = 72
columns and fits ONE 128-wide MXU tile, so x_hi, x_mid and x_lo against
it are three MXU passes where six passes of 24 columns leave the other
104 idle; `g P^T` and `X^T g` use the same packed operand.

The rule (`_resolve_path`) adapts on what the lowering sees: on a TPU,
a float32 stream whose lanes are whole 128-lane tiles and at most five
of them (the packed projection must fit one tile) takes the kernels;
anything else, and every CPU, the jax.numpy form. `force=` ("pallas" /
"interpret" / "xla") is for tests and probes. `ptpu_hc_lowerings_total
{lanes, sinkhorn_iters, path, stage}` counts, at trace time, the path
each "mix" and "merge" took.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import register
from ..monitor import metrics as _metrics
from .flash_attention import _largest_divisor, _on_tpu

_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_hc_lowerings_total",
    "lowerings of a hyper-connection's coefficients at trace time (one a "
    "sublayer, none a step): the lanes of the residual stream and the "
    "Sinkhorn-Knopp rounds that make its residual mix doubly stochastic",
    ("lanes", "sinkhorn_iters", "path", "stage"))


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


# --------------------------------------------------------------------------
# the kernels. A grid step holds WHOLE rows of the stream, [rows, n d]
# float32, and walks them in chunks of `bc` lanes (a fori_loop, so that a
# body is traced once and not n d / bc times); lane i's chunk is the
# columns [i d + k0, i d + k0 + bc), whole lane tiles.
_LANES = 128
_CHUNK_LANES = 512
# bytes of stream blocks a grid step may hold in VMEM, the double
# buffering counted: "mix" holds one such block (x), "merge" two (x and
# X'), the backward kernels three (two in, one out). Measured on one TPU
# v5e at [4096, 4 x 3584], ms a call alone (my chip run, PR 43; XLA's own
# copy of the stream, one read and one write, takes 0.720):
#   rows a step   hc_mix_fwd  hc_merge_fwd  hc_merge_bwd  hc_mix_bwd
#        16                      0.765         1.086        2.957
#        32         0.479        0.762         1.085        1.438
#        64         0.456        0.760         1.084        1.092
#       128         0.439        0.759         1.085        1.060
#       256         0.432
# The merges are bandwidth at any size; hc_mix_bwd's X^T g contracts
# over a block's rows and starves the MXU under 64. Chunks of 256 to
# 1792 lanes read the same to 2%; all 3584 run out of VMEM. So 24 MB:
# 128 rows for hc_mix_fwd, 64 for the other three.
_STREAM_BYTES = 24 * 1024 * 1024
# beside the blocks: a chunk's temporaries ([rows, 512] float32 values,
# 128 KB each at 64 rows) and the sums carried across the chunks. Compiled
# for a described v5e at the cell's shape, "merge" is refused with 0 and
# every kernel fits with 1 MB
_SPARE_BYTES = 2 * 1024 * 1024


def _block_rows(rows, width, streams):
    """Rows of a grid step: whole sublane tiles, at most what
    `_STREAM_BYTES` hold of `streams` float32 blocks [., width] twice
    over, and a divisor of `rows` where there is one."""
    most = max(_STREAM_BYTES // (2 * streams * width * 4) // 8, 1)
    if rows % 8:
        return 8 * min(most, -(-rows // 8))
    return 8 * _largest_divisor(rows // 8, most)


def _chunk(d):
    return _LANES * _largest_divisor(d // _LANES, _CHUNK_LANES // _LANES)


def _chunks(d, bc, body, carry):
    """`carry = body(k0, carry)` for the chunks' first columns k0."""
    if d == bc:
        return body(0, carry)
    return lax.fori_loop(
        0, d // bc, lambda k, c: body(pl.multiple_of(k * bc, _LANES), c),
        carry)


def _fold(p):
    """[rows, bc] -> [rows, 128]: the lane tiles added (no shuffle)."""
    return sum(lax.slice_in_dim(p, t, t + _LANES, axis=1)
               for t in range(0, p.shape[1], _LANES))


def _cols(like):
    return lax.broadcasted_iota(jnp.int32, like.shape, 1)


def _column(v, k):
    """Column k of v [rows, 128] as [rows, 1]."""
    return lax.slice_in_dim(v, k, k + 1, axis=1)


def _spread(columns, like):
    """[rows, 128] holding `columns[k]` ([rows, 128] partial sums) summed
    into column k."""
    out, cols = jnp.zeros(like.shape, jnp.float32), _cols(like)
    for k, part in enumerate(columns):
        out = jnp.where(cols == k, jnp.sum(part, -1, keepdims=True), out)
    return out


def _split3(x):
    """float32 x as three bfloat16 parts, hi + mid + lo = x to 2^-24:
    the operands of `Precision.HIGHEST`'s six products."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _shift(v, k, c):
    """v [rows, 128] with column j + k c brought to column j (k < 0:
    sent there)."""
    return pltpu.roll(v, (-k * c) % _LANES, 1)


def _dot(a, b, contract):
    return lax.dot_general(a, b, ((contract, ((), ()))),
                           preferred_element_type=jnp.float32)


def _sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def _packed_proj(proj, c):
    """[P_hi | P_mid | P_lo | 0] bfloat16 [n d, 128]: ONE MXU tile of
    columns carries all three parts of the projection, so that
    x_hi, x_mid and x_lo against it are three passes for the six
    products of `Precision.HIGHEST` (hi hi, hi mid, hi lo, mid hi,
    mid mid, lo hi), where six passes of 24 columns each leave the
    tile's other 104 idle."""
    parts = _split3(proj.astype(jnp.float32))
    return jnp.pad(jnp.concatenate(parts, 1),
                   ((0, 0), (0, _LANES - 3 * c)))


def _pre_rows(alpha_pre, bias_pre, n):
    """(alpha_pre in the columns of H_pre, 0 elsewhere; bias_pre
    likewise), float32 [1, 128] each."""
    pad = lambda v: jnp.pad(v.astype(jnp.float32).reshape(1, n),
                            ((0, 0), (0, _LANES - n)))
    return pad(jnp.broadcast_to(alpha_pre, (n,))), pad(bias_pre)


def _mix_fwd_kernel(x_ref, w_ref, a_ref, b_ref, h_ref, zs_ref, *, n, d, c,
                    bc, eps):
    zero = jnp.zeros((x_ref.shape[0], _LANES), jnp.float32)

    def project(k0, carry):
        ss, hi, mid, lo = carry
        for i in range(n):
            at = pl.ds(i * d + k0, bc)
            x, w = x_ref[:, at], w_ref[at, :]
            ss = ss + _fold(x * x)
            a, b, r = _split3(x)
            hi = hi + _dot(a, w, ((1,), (0,)))
            mid = mid + _dot(b, w, ((1,), (0,)))
            lo = lo + _dot(r, w, ((1,), (0,)))
        return ss, hi, mid, lo

    ss, hi, mid, lo = _chunks(d, bc, project, (zero,) * 4)
    inv = lax.rsqrt(jnp.sum(ss, -1, keepdims=True) / (n * d) + eps)
    # the six products, the small ones first: lo hi, hi lo, mid mid,
    # mid hi, hi mid, hi hi
    u = (lo + _shift(hi, 2, c) + _shift(mid, 1, c) + mid + _shift(hi, 1, c)
         + hi)
    cols = _cols(u)
    zs = jnp.where(cols < c, u * inv, 0.0)
    pre = _sigmoid(zs * a_ref[...] + b_ref[...])
    zs_ref[...] = jnp.where(cols == c, inv, zs)
    weights = [_column(pre, i) for i in range(n)]

    def weigh(k0, _):
        h_ref[:, pl.ds(k0, bc)] = sum(
            weights[i] * x_ref[:, pl.ds(i * d + k0, bc)] for i in range(n))
        return 0

    _chunks(d, bc, weigh, 0)


def _mix_bwd_kernel(x_ref, dh_ref, dzs_ref, zs_ref, dxa_ref, w_ref, a_ref,
                    b_ref, dx_ref, dzp_ref, dpt_ref, *, n, d, c, bc, rows):
    bm = x_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        dpt_ref[...] = jnp.zeros(dpt_ref.shape, jnp.float32)

    saved = zs_ref[...]
    inv = _column(saved, c)
    zs = jnp.where(_cols(saved) < c, saved, 0.0)
    alpha = a_ref[...]
    pre = _sigmoid(zs * alpha + b_ref[...])
    ragged = rows % bm != 0
    if ragged:      # the last block's rows past the stream's end
        real = (pl.program_id(0) * bm + lax.broadcasted_iota(
            jnp.int32, (bm, 1), 0)) < rows

    def reduce(k0, parts):
        dh = dh_ref[:, pl.ds(k0, bc)]
        return tuple(p + _fold(dh * x_ref[:, pl.ds(i * d + k0, bc)])
                     for i, p in enumerate(parts))

    zero = jnp.zeros((bm, _LANES), jnp.float32)
    dzp = _spread(_chunks(d, bc, reduce, (zero,) * n), zero) \
        * pre * (1.0 - pre)
    gz = jnp.where(_cols(saved) < c, dzs_ref[...], 0.0) + alpha * dzp
    if ragged:
        gz, dzp = jnp.where(real, gz, 0.0), jnp.where(real, dzp, 0.0)
    dzp_ref[...] = dzp
    # d mean(x^2): -x inv^3 / (n d) sum_c gz_c (x P)_c, and zs = (x P) inv
    pull = jnp.sum(gz * zs, -1, keepdims=True) * inv * inv / (n * d)
    g = [part.astype(jnp.float32) for part in _split3(gz * inv)]
    bf = lambda v: v.astype(jnp.bfloat16)
    # g P^T against the packed [P_hi | P_mid | P_lo]: g_hi meets all
    # three, g_mid the first two, g_lo the first
    back = [bf(g[0] + _shift(g[0], -1, c) + _shift(g[0], -2, c)),
            bf(g[1] + _shift(g[1], -1, c)), bf(g[2])]
    # X^T g as [g_hi | g_mid | g_lo]^T x_hi + [g_hi | g_mid]^T x_mid +
    # g_hi^T x_lo: rows [k c, (k + 1) c) of dpt, summed outside
    fore = [bf(g[0] + _shift(g[1], -1, c) + _shift(g[2], -2, c)),
            bf(g[0] + _shift(g[1], -1, c)), bf(g[0])]
    weights = [_column(pre, i) for i in range(n)]
    kept = dpt_ref.shape[0]

    def sweep(k0, _):
        dh = dh_ref[:, pl.ds(k0, bc)]
        for i in range(n):
            at = pl.ds(i * d + k0, bc)
            x, w = x_ref[:, at], w_ref[at, :]
            if ragged:
                x = jnp.where(real, x, 0.0)
            through = sum(_dot(part, w, ((1,), (1,)))
                          for part in reversed(back))
            dx_ref[:, at] = dxa_ref[:, at] + weights[i] * dh + through \
                - x * pull
            dpt_ref[:, at] += sum(
                _dot(lhs, part, ((0,), (0,)))
                for lhs, part in zip(reversed(fore), reversed(_split3(x)))
            )[:kept]
        return 0

    _chunks(d, bc, sweep, 0)


def _merge_fwd_kernel(x_ref, y_ref, cf_ref, o_ref, *, n, d, bc):
    cf = cf_ref[...]
    cs = [_column(cf, k) for k in range(n * n + n)]

    def body(k0, _):
        y = y_ref[:, pl.ds(k0, bc)].astype(jnp.float32)
        xs = [x_ref[:, pl.ds(j * d + k0, bc)] for j in range(n)]
        for i in range(n):
            o_ref[:, pl.ds(i * d + k0, bc)] = sum(
                cs[i * n + j] * xs[j] for j in range(n)) + cs[n * n + i] * y
        return 0

    _chunks(d, bc, body, 0)


def _merge_bwd_kernel(g_ref, x_ref, y_ref, cf_ref, dxa_ref, dy_ref, dcf_ref,
                      *, n, d, bc):
    cf = cf_ref[...]
    cs = [_column(cf, k) for k in range(n * n + n)]

    def body(k0, parts):
        y = y_ref[:, pl.ds(k0, bc)].astype(jnp.float32)
        gs = [g_ref[:, pl.ds(i * d + k0, bc)] for i in range(n)]
        xs = [x_ref[:, pl.ds(j * d + k0, bc)] for j in range(n)]
        dy_ref[:, pl.ds(k0, bc)] = sum(
            cs[n * n + i] * gs[i] for i in range(n)).astype(dy_ref.dtype)
        for j in range(n):
            dxa_ref[:, pl.ds(j * d + k0, bc)] = sum(
                cs[i * n + j] * gs[i] for i in range(n))
        sums = [_fold(gs[i] * xs[j]) for i in range(n) for j in range(n)] \
            + [_fold(gs[i] * y) for i in range(n)]
        return tuple(p + s for p, s in zip(parts, sums))

    zero = jnp.zeros(cf.shape, jnp.float32)
    dcf_ref[...] = _spread(
        _chunks(d, bc, body, (zero,) * (n * n + n)), zero)


def _stream_call(kernel, name, ins, outs, bm, interpret, carried=False,
                 aliases=None, **static):
    """`kernel` over row blocks of `bm`: `ins` and `outs` are (array or
    ShapeDtypeStruct, "rows" | "whole") pairs: a block of `bm` whole
    rows, or the whole array resident across the grid."""
    rows = ins[0][0].shape[0]

    def spec(a, how):
        if how == "rows":
            return pl.BlockSpec((bm, a.shape[1]), lambda r: (r, 0))
        # resident: fetched once, so one buffer and not two
        return pl.BlockSpec(a.shape, lambda r: (0, 0),
                            pipeline_mode=pl.Buffered(1))

    return pl.pallas_call(
        functools.partial(kernel, **static),
        grid=(pl.cdiv(rows, bm),),
        in_specs=[spec(a, how) for a, how in ins],
        out_specs=[spec(a, how) for a, how in outs],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a, _ in outs],
        input_output_aliases=aliases or {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if carried else "parallel",),
            vmem_limit_bytes=_vmem_bytes(bm, ins, outs)),
        interpret=interpret, name=name,
    )(*[a for a, _ in ins])


def _vmem_bytes(bm, ins, outs):
    """What the blocks take, the row blocks twice over for the double
    buffering and the resident ones once, and `_SPARE_BYTES` for the
    chunks' temporaries."""
    size = lambda a, how: (2 * bm if how == "rows" else a.shape[0]) \
        * -(-a.shape[1] // _LANES) * _LANES * jnp.dtype(a.dtype).itemsize
    return sum(size(a, how) for a, how in list(ins) + list(outs)) \
        + _SPARE_BYTES


def _shape(rows, width, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((rows, width), dtype)


# jitted as the flash kernels are: a stack of layers traces and lowers
# each kernel once. static: the lanes n, a grid step's rows bm, interpret
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _mix_fwd(x, w, a_row, b_row, n, eps, bm, interpret):
    rows, width = x.shape
    d, c = width // n, n * (n + 2)
    return _stream_call(
        _mix_fwd_kernel, "hc_mix_fwd",
        [(x, "rows"), (w, "whole"), (a_row, "whole"), (b_row, "whole")],
        [(_shape(rows, d), "rows"), (_shape(rows, _LANES), "rows")],
        bm, interpret, n=n, d=d, c=c, bc=_chunk(d), eps=eps)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _mix_bwd(x, dh, dzs, zs, dxa, w, a_row, b_row, n, bm, interpret):
    rows, width = x.shape
    d, c = width // n, n * (n + 2)
    return _stream_call(
        _mix_bwd_kernel, "hc_mix_bwd",
        [(x, "rows"), (dh, "rows"), (dzs, "rows"), (zs, "rows"),
         (dxa, "rows"), (w, "whole"), (a_row, "whole"), (b_row, "whole")],
        [(_shape(rows, width), "rows"), (_shape(rows, _LANES), "rows"),
         (_shape(-(-3 * c // 8) * 8, width), "whole")],
        bm, interpret, carried=True, aliases={4: 0},
        n=n, d=d, c=c, bc=_chunk(d), rows=rows)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _merge_fwd(x, y, cf, n, bm, interpret):
    rows, width = x.shape
    return _stream_call(
        _merge_fwd_kernel, "hc_merge_fwd",
        [(x, "rows"), (y, "rows"), (cf, "rows")],
        [(_shape(rows, width), "rows")],
        bm, interpret, n=n, d=width // n, bc=_chunk(width // n))[0]


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _merge_bwd(g, x, y, cf, n, bm, interpret):
    rows, width = x.shape
    d = width // n
    return _stream_call(
        _merge_bwd_kernel, "hc_merge_bwd",
        [(g, "rows"), (x, "rows"), (y, "rows"), (cf, "rows")],
        [(_shape(rows, width), "rows"), (_shape(rows, d, y.dtype), "rows"),
         (_shape(rows, _LANES), "rows")],
        bm, interpret, aliases={0: 0}, n=n, d=d, bc=_chunk(d))


# x [N, n d], proj [n d, n (n + 2)], alpha_pre [], bias_pre [n], all
# float32 -> h [N, d], zs [N, 128] (the normed projection in its first n (n + 2)
# columns, rsqrt(mean(x^2) + eps) in the next), and x itself: the stream
# handed through, so that "merge"'s cotangent of it arrives in THIS
# backward rule and is added to "mix"'s in the kernel's one visit.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _mix(x, proj, alpha_pre, bias_pre, n, eps, interpret):
    return _mix_rule(x, proj, alpha_pre, bias_pre, n, eps, interpret)[0]


def _mix_rule(x, proj, alpha_pre, bias_pre, n, eps, interpret):
    w = _packed_proj(proj, n * (n + 2))
    rows = _pre_rows(alpha_pre, bias_pre, n)
    h, zs = _mix_fwd(x, w, *rows, n, eps, _block_rows(*x.shape, 1),
                     interpret)
    return (h, zs, x), (x, zs, w, rows)


def _mix_pull(n, eps, interpret, saved, cotangents):
    x, zs, w, rows = saved
    dh, dzs, dxa = cotangents
    c = n * (n + 2)
    dx, dzp, dpt = _mix_bwd(x, dh, dzs, zs, dxa, w, *rows, n,
                            _block_rows(*x.shape, 3), interpret)
    dproj = (dpt[:c] + dpt[c:2 * c] + dpt[2 * c:3 * c]).T
    dzp = dzp[:, :n]
    return dx, dproj, jnp.sum(dzp * zs[:, :n]), jnp.sum(dzp, 0)


_mix.defvjp(_mix_rule, _mix_pull)


# x [N, n d], y [N, d], cf [N, 128]: H_res row major in its first n n
# columns, H_post in the next n
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _merge(x, y, cf, n, interpret):
    return _merge_fwd(x, y, cf, n, _block_rows(*x.shape, 2), interpret)


def _merge_rule(x, y, cf, n, interpret):
    return _merge(x, y, cf, n, interpret), (x, y, cf)


def _merge_pull(n, interpret, saved, g):
    x = saved[0]
    return tuple(_merge_bwd(g, *saved, n, _block_rows(*x.shape, 3),
                            interpret))


_merge.defvjp(_merge_rule, _merge_pull)


def _resolve_path(x, lanes, force):
    """ "pallas" / "interpret" / "xla": auto takes the kernels on a TPU
    where the stream is float32, a lane is whole lane tiles and the
    packed projection fits one MXU tile (3 n (n + 2) <= 128: up to five
    lanes); the jax.numpy form anywhere else."""
    c = lanes * (lanes + 2)
    usable = (x.dtype == jnp.float32 and x.shape[-1] % (lanes * _LANES) == 0
              and 3 * c <= _LANES)
    if force is None:
        return "pallas" if usable and _on_tpu(x) else "xla"
    if force != "xla" and not usable:
        raise ValueError(
            "hyper_connection: the kernels want a float32 stream of at most "
            "5 lanes of whole 128-lane tiles, got %s %s in %d lanes"
            % (x.dtype, x.shape, lanes))
    return force


def mix_stage(x, proj, alpha, bias, lanes, iters, eps, clamp, norm_eps=1e-6,
              force=None):
    """The stage "mix" of x [N, n d]: (H_pre X [N, d], H_post [N, n],
    H_res [n, n, N], the stream for "merge" to read: x itself).

    force: None = auto, "pallas" / "interpret" / "xla" pin a path (tests
    run the kernels on the CPU with "interpret")."""
    n = lanes
    path = _resolve_path(x, n, force)
    _LOWERINGS.inc(lanes=str(n), sinkhorn_iters=str(iters), path=path,
                   stage="mix")
    if path == "xla":
        pre, post, res = coefficients(x, proj, alpha, bias, n, iters, eps,
                                      clamp, norm_eps)
        return mix_in(x, pre, n), post, res, x
    c = n * (n + 2)
    proj, alpha, bias = (v.astype(jnp.float32) for v in (proj, alpha, bias))
    h, zs, through = _mix(x, proj, alpha[0], bias[:n], n, float(norm_eps),
                          path == "interpret")
    z = zs[:, n:c] * jnp.repeat(alpha[1:], np.array([n, n * n])) + bias[n:]
    post = 2.0 * jax.nn.sigmoid(z[:, :n])
    res = jnp.exp(jnp.clip(z[:, n:], *clamp)).T.reshape(n, n, -1)
    return h, post, sinkhorn(res, iters, eps), through


def merge_stage(x, post, res, y, lanes, force=None):
    """The stage "merge": H_res X + H_post^T y, [N, n d] float32, of the
    stream x as "mix" handed it through."""
    n = lanes
    path = _resolve_path(x, n, force)
    _LOWERINGS.inc(lanes=str(n), sinkhorn_iters="", path=path, stage="merge")
    if path == "xla":
        return merge(x, post, res, y, n)
    cf = jnp.concatenate([res.reshape(n * n, -1).T, post], 1)
    cf = jnp.pad(cf.astype(jnp.float32), ((0, 0), (0, _LANES - n * n - n)))
    return _merge(x, y, cf, n, path == "interpret")


@register("hyper_connection")
def _hyper_connection(ctx, op):
    """Stages (attr `stage`) over X [B, T, .], flattened to rows:
    "widen" X [.., d] -> Out [.., n d]; "mix" X, Proj, Alpha, Bias ->
    Out = H_pre X [.., d], Post [B*T, n], Res [n, n, B*T], Through = X;
    "merge" X (what "mix" handed through), Post, Res, Y -> Out [.., n d];
    "narrow" X -> Out [.., d]."""
    stage, n = op.attr("stage"), int(op.attr("lanes"))
    x = ctx.in1(op, "X")
    lead = x.shape[:-1]
    rows = x.reshape(-1, x.shape[-1])
    if stage == "widen":
        out = jnp.tile(rows.astype(jnp.float32), (1, n))
    elif stage == "narrow":
        out = sum(_lanes(rows, n))
    elif stage == "mix":
        out, post, res, through = mix_stage(
            rows, ctx.in1(op, "Proj"), ctx.in1(op, "Alpha"),
            ctx.in1(op, "Bias"), n, int(op.attr("sinkhorn_iters")),
            float(op.attr("sinkhorn_eps")),
            (float(op.attr("clamp_min")), float(op.attr("clamp_max"))),
            float(op.attr("epsilon", 1e-6)))
        ctx.set_out(op, "Post", post)
        ctx.set_out(op, "Res", res)
        ctx.set_out(op, "Through", through.reshape(x.shape))
    elif stage == "merge":
        y = ctx.in1(op, "Y")
        out = merge_stage(rows, ctx.in1(op, "Post"), ctx.in1(op, "Res"),
                          y.reshape(-1, y.shape[-1]), n)
    else:
        raise ValueError("hyper_connection: no stage %r" % (stage,))
    ctx.set_out(op, "Out", out.reshape(lead + (out.shape[-1],)))


# pallas imports placed at the end, as in flash_attention.py: a CPU-only
# environment that never takes the kernel path still imports this module
from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402
