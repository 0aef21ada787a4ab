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

Row by row the rule is T dependent rank-one updates
(``delta_rule_steps``: the tests' truth, ``force="steps"``). The paths
a step takes walk CHUNKS of C rows. Inside a chunk, with ``c_i`` the
running sum of g up to row i, ``D_ij = exp(c_i - c_j)`` for ``i >= j``
and ``M = tril(diag(beta) (K K^T . D), -1)``, the rows' updates solve a
unit lower triangular system once::

    T_  = (I + M)^-1 = (I + N)(I + N^2)(I + N^4) ... , N = -M

(a strictly lower N of C rows has ``N^C = 0``, so the product of
``log2 C`` factors IS the inverse), ``W = T_ (diag(beta exp(c)) K)``,
``U = T_ (diag(beta) V)``. Across chunks the float32 state ``S`` ``[d_k,
d_v]`` of a head is carried: ``V' = U - W S``, the chunk's rows of the
output ``(diag(exp(c)) Q) S + tril(Q K^T . D) V'``, and ``S <- exp(c_C)
S + (diag(exp(c_C - c)) K)^T V'``. The solve, the decays and every
product with the state or with ``T_`` are float32 at ``highest`` (a
float32 product at the default precision rounds its operands to
bfloat16 on a TPU); the two Gram products ``K K^T`` and ``Q K^T`` take
the operands as they come, bfloat16 under AMP, whose products are exact
in the float32 they are summed in.

Two forms of the walk, one arithmetic (``_resolve_path``):

* ``delta_rule_chunked``, ``jax.numpy``: every chunk's ``T_``, ``W``,
  ``U`` at once as batched products over ``[B, H, T / C]``, a
  ``lax.scan`` over the chunks for the state, the backward autodiff's.
  Every CPU takes it, and a head whose state does not fit the kernels.
* the kernel pair ``delta_rule_fwd`` / ``delta_rule_bwd`` under ONE
  ``jax.custom_vjp`` (ISSUE 54): on a TPU, keys of at most 128 and
  values of at most 256. Grid ``(B H, T / C)``, a head's chunks in
  turn, its state in a VMEM scratch for the whole walk. The forward
  reads a chunk of q, k, v ``[C, d]`` (a head's rows together, ``[B H,
  T, d]``: XLA turns ``[B, T, H, d]`` once on the way in and ``o`` once
  on the way out) and its gates (``c`` is summed by XLA before the
  call: ``[B, T, H]`` float32), makes everything above in VMEM, and
  writes the chunk's rows of ``o`` and the chunk's STARTING state
  (``[B H, T / C, d_k, d_v]`` float32: 141 MB a layer at the cell's
  shape and chunks of 64). The backward walks the chunks last to
  first with the cotangent of the state carried the same way: it reads
  the chunk's inputs, its starting state and ``do``, makes ``T_``,
  ``W``, ``U``, ``V'`` and the masked ``Q K^T . D`` again (cheaper than
  their bytes), and writes the chunk's rows of dq, dk, dv, of beta's
  cotangent and of ``c``'s, which XLA sums back into g's (``_bwd_kernel``
  has the chunk's equations transposed; ``dM = -T_^T dT_ T_^T``). No
  ``[C, C]`` value reaches HBM and no ``while`` op is left. The gates
  ride as rows (``[8, C]`` a chunk); the kernels turn them into columns
  and the backward's column sums into rows by products with the
  identity, exact at ``highest``. 96 and 192 are no whole lane tiles:
  the blocks are a head's whole width, which Mosaic pads in VMEM.

T is padded to a multiple of the chunk with zero rows (``g`` 0, ``beta``
0): a padded row decays nothing and adds nothing. Each lowering counts
itself in ``ptpu_delta_rule_lowerings_total{path, chunk, heads, d_k,
d_v}``; the device rows carry the Program op's scope
``gated_delta_rule``; a ``layers.recompute`` region may keep the op
under the kind `DELTA_OUT` (``ops/control_flow.py``,
``kept_by_a_region``): under the kernels the result and the chunks'
starting states (`DELTA_STATES`), all that the backward kernel reads of
the forward, so that the forward kernel is dead in the region's second
forward; under the ``jax.numpy`` walk the result alone, which spares
the two output products: that backward reads the chunk states and
``T_``, which are made again.
"""

import functools
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..core.registry import register
from ..monitor import metrics as _metrics
from .flash_attention import _on_tpu

CHUNK = 64          # rows of a chunk of the jax.numpy walk
# and of the kernels'. Chunks of 128 are faster (forward + backward
# 11.9 ms a layer at the cell's shape for 14.1: a pass of 128 rows fills
# the MXU's tile) and NOT taken: the doubling product forms N^64, and
# on one seed of twelve the cell's logits error read 7.2e-2 for 4.8e-3
# (my chip runs, PR 54; PERF.md section 6)
KERNEL_CHUNK = 64
_LANES = 128
_F32 = jnp.float32
# what the kernels carry across a head's chunks (the state, and its
# cotangent) and what the forward saves for the backward (the chunks'
# starting states): float32, the configuration's (`train_dtype`).
# Constants, not options: tests/test_delta_rule.py plants bfloat16 in
# each to show that its float32 test would notice.
_CARRIED = _SAVED = jnp.float32
_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_delta_rule_lowerings_total",
    "gated delta rule lowerings at trace time (one a lowering of the op, "
    "none a step): the path taken (pallas: the kernel pair delta_rule_fwd "
    "/ delta_rule_bwd, a TPU's where a head's state fits VMEM; interpret: "
    "the same on the CPU, tests only; chunked: the jax.numpy chunk walk, "
    "every other device and shape; steps: lax.scan over T, tests only), "
    "the chunk's rows (0 on the steps path), the heads and a head's key "
    "and value widths",
    ("path", "chunk", "heads", "d_k", "d_v"))
# the names of the op's result and, under the kernels, of the chunks'
# starting states, where a recompute region keeps them
DELTA_OUT, DELTA_STATES = "delta_rule_out", "delta_rule_states"


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


def unit_lower_inverse(n, times=None, eye=None):
    """``(I - n)^-1`` of a strictly lower triangular n ``[..., C, C]``:
    ``(I + n)(I + n^2)(I + n^4) ...``, exact since ``n^C = 0``.
    `times` is the product (a kernel hands its own, and its `eye`)."""
    c = n.shape[-1]
    times = times or functools.partial(_mm, "...ij,...jk->...ik")
    eye = jnp.eye(c, dtype=n.dtype) if eye is None else eye
    inv, power, reach = eye + n, n, 2
    while reach < c:
        power = times(power, power)
        inv = inv + times(inv, power)
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


# -- the kernels ------------------------------------------------------------

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, dims):
    """A product inside a kernel, summed in float32: float32 operands
    at ``highest`` (Mosaic's ``contract_precision<fp32>``), bfloat16
    operands as they are."""
    if a.dtype != b.dtype:
        a, b = a.astype(_F32), b.astype(_F32)
    # (Mosaic takes the precision on float32 operands alone)
    return lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=_F32,
        precision=lax.Precision.HIGHEST if a.dtype == _F32 else None)


def _chunk_parts(q, k, v, gates):
    """All of a chunk that does not read the state, from its blocks: q
    and k [C, d_k], v [C, d_v], gates [8, C] float32 (row 0 the running
    sum ``c`` of g inside the chunk, row 1 beta, row 2 ``c_C`` in
    every lane). The module docstring's names."""
    c = q.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = (rows == cols).astype(_F32)
    # the gates' rows as columns: a product with the identity, exact
    # at `highest`
    down = _dot(eye, gates, _NT)                        # [C, 8]
    run, beta = down[:, 0:1], down[:, 1:2]
    run_row = gates[0:1, :]
    # c_C [1, 1], by a reduction over a row that holds it in every
    # lane: a reduction's result spreads over a tile, which one lane cut
    # out of a row does not
    last = jnp.max(gates[2:3, :], 1, keepdims=True)
    seen, below = rows >= cols, rows > cols
    decay = jnp.exp(jnp.where(seen, run - run_row, -jnp.inf))
    kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
    m = jnp.where(below, beta * decay * kk, 0.0)
    inv = unit_lower_inverse(-m, lambda a, b: _dot(a, b, _NN), eye)
    up = jnp.exp(run)
    q32, k32, v32 = (x.astype(_F32) for x in (q, k, v))
    kb, vb = k32 * (beta * up), v32 * beta
    return types.SimpleNamespace(
        eye=eye, run=run, beta=beta, last=last,
        seen=seen, below=below, decay=decay, kk=kk, m=m, inv=inv, up=up,
        q32=q32, k32=k32, v32=v32, kb=kb, vb=vb,
        w=_dot(inv, kb, _NN), u=_dot(inv, vb, _NN),
        k_out=k32 * jnp.exp(last - run), q_up=q32 * up,
        within=jnp.where(seen, decay * qk, 0.0))


def _chunk_forward(q, k, v, gates, s):
    """(the chunk's rows of o, float32; the state it hands on)."""
    p = _chunk_parts(q, k, v, gates)
    fresh = p.u - _dot(p.w, s, _NN)
    return (_dot(p.q_up, s, _NN) + _dot(p.within, fresh, _NN),
            s * jnp.exp(p.last) + _dot(p.k_out, fresh, _TN))


def _chunk_backward(q, k, v, gates, s, ds, do):
    """The chunk's equations transposed: from the state `s` it started
    with, the cotangent `ds` of the state it hands on and `do`
    (float32), (dq, dk, dv, dgates [8, C]: row 0 the cotangent of
    ``c``, row 1 beta's; the cotangent of `s`)."""
    p = _chunk_parts(q, k, v, gates)
    inv, decay, beta, up = p.inv, p.decay, p.beta, p.up
    k32, kb, k_out, q_up = p.k32, p.kb, p.k_out, p.q_up
    a_last = jnp.exp(p.last)
    fresh = p.u - _dot(p.w, s, _NN)
    # o = q_up s + within fresh; s' = a_last s + k_out^T fresh
    dfresh = _dot(p.within, do, _TN) + _dot(k_out, ds, _NN)
    dq_up = _dot(do, s, _NT)
    dwithin = jnp.where(p.seen, _dot(do, fresh, _NT), 0.0)
    dk_out = _dot(fresh, ds, _NT)
    # fresh = u - w s; w = inv kb; u = inv vb
    dw = -_dot(dfresh, s, _NT)
    ds_before = _dot(q_up, do, _TN) + a_last * ds - _dot(p.w, dfresh, _TN)
    dinv = _dot(dw, kb, _NT) + _dot(dfresh, p.vb, _NT)
    dkb, dvb = _dot(inv, dw, _TN), _dot(inv, dfresh, _TN)
    # inv = (I + m)^-1: dm = -inv^T dinv inv^T, strictly lower
    dm = jnp.where(p.below, -_dot(_dot(inv, dinv, _TN), inv, _NT), 0.0)
    dkk, dqk = dm * (beta * decay), dwithin * decay
    dq = _dot(dqk, k32, _NN) + dq_up * up
    dk = _dot(dkk, k32, _NN) + _dot(dkk, k32, _TN) \
        + _dot(dqk, p.q32, _TN) + dkb * (beta * up) \
        + dk_out * jnp.exp(p.last - p.run)
    # through the decays: d c_i of exp(c_i - c_j), of exp(c_i) and of
    # exp(c_C - c_i); d c_C at the chunk's last row
    through = dm * p.m + dwithin * p.within
    across = lambda x: jnp.sum(x, 1, keepdims=True)
    total = lambda x: jnp.sum(across(x), 0, keepdims=True)
    tail = dk_out * k_out
    drun = across(through) + across(dq_up * q_up + dkb * kb - tail)
    dbeta = across(dm * decay * p.kk) + across(dkb * (k32 * up)) \
        + across(dvb * p.v32)
    c = do.shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (c, _LANES), 1)
    flat = _dot(jnp.where(lane == 0, drun, jnp.where(lane == 1, dbeta, 0.0)),
                p.eye, _TN)[0:8]                     # columns to rows
    at = lax.broadcasted_iota(jnp.int32, (8, c), 1)
    row = lax.broadcasted_iota(jnp.int32, (8, c), 0)
    end = total(tail) + a_last * total(ds * s)
    dgates = flat + jnp.where(
        row == 0, jnp.where(at == c - 1, end, 0.0)
        - jnp.sum(through, 0, keepdims=True), 0.0)
    return dq, dk, dvb * beta, dgates, ds_before


def _fwd_kernel(q_ref, k_ref, v_ref, gates_ref, o_ref, states_ref, s_scr):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    s = s_scr[...].astype(_F32)
    states_ref[0, 0] = s.astype(states_ref.dtype)
    o, s = _chunk_forward(q_ref[0], k_ref[0], v_ref[0], gates_ref[0, 0], s)
    o_ref[0] = o.astype(o_ref.dtype)
    s_scr[...] = s.astype(s_scr.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, gates_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgates_ref, ds_scr):
    """Last chunk first; ``ds_scr`` carries the cotangent of the state
    a chunk hands on."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, ds_scr.dtype)

    dq, dk, dv, dgates, ds = _chunk_backward(
        q_ref[0], k_ref[0], v_ref[0], gates_ref[0, 0],
        states_ref[0, 0].astype(_F32), ds_scr[...].astype(_F32),
        do_ref[0].astype(_F32))
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dgates_ref[0, 0] = dgates
    ds_scr[...] = ds.astype(ds_scr.dtype)


def _operands(q, k, v, g, beta, chunk):
    """The kernels' operands: a head's rows together, ``[B H, T', d]``
    with T padded to whole chunks, and the gates ``[B H, T' / C, 8,
    C]`` float32: row 0 the running sum of g inside each chunk, row 1
    beta, row 2 the chunk's whole sum in every lane."""
    b, t, h, _ = k.shape
    n = -(-t // chunk)
    first = functools.partial(_heads_first, rows=n * chunk)
    cut = lambda x: first(x.astype(_F32)).reshape(b * h, n, 1, chunk)
    run = jnp.cumsum(cut(g), -1)
    gates = jnp.concatenate(
        [run, cut(beta), jnp.broadcast_to(run[..., -1:], run.shape),
         jnp.zeros((b * h, n, 5, chunk), _F32)], 2)
    return n, (first(q), first(k), first(v), gates)


def _heads_first(x, rows):
    """[B, T, H, ..] -> [B H, rows, ..], T padded with zero rows."""
    b, t, h = x.shape[:3]
    x = jnp.pad(x, [(0, 0), (0, rows - t)] + [(0, 0)] * (x.ndim - 2))
    return jnp.moveaxis(x, 2, 1).reshape((b * h, rows) + x.shape[3:])


def _heads_last(x, like):
    """[B H, T', ..] -> [B, T, H, ..] of `like`."""
    b, t, h = like.shape[:3]
    x = x.reshape((b, h, x.shape[1]) + x.shape[2:])
    return jnp.moveaxis(x, 1, 2)[:, :t]


def _specs(chunk, d_k, d_v, at):
    """BlockSpecs of (q or k, v, the gates, the saved states), `at`
    mapping the grid's chunk index to the chunk walked."""
    return (pl.BlockSpec((1, chunk, d_k), lambda i, t: (i, at(t), 0)),
            pl.BlockSpec((1, chunk, d_v), lambda i, t: (i, at(t), 0)),
            pl.BlockSpec((1, 1, 8, chunk), lambda i, t: (i, at(t), 0, 0)),
            pl.BlockSpec((1, 1, d_k, d_v), lambda i, t: (i, at(t), 0, 0)))


def _params():
    # (no VMEM limit of its own: a chunk's blocks, the state and the
    # chunk's float32 parts fit the compiler's 16 MiB)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


# jitted, as the flash kernels' wrappers are: a stack of layers traces
# and lowers each kernel once, and the kernels keep their own names in
# the compiled program whatever transformation traced the call
@functools.partial(jax.jit, static_argnums=(5, 6))
def _fwd_pallas(q, k, v, g, beta, chunk, interpret):
    """(o [B, T, H, d_v], the chunks' starting states [B H, T' / C,
    d_k, d_v] float32)."""
    n, ops = _operands(q, k, v, g, beta, chunk)
    (bh, rows, d_k), d_v = ops[0].shape, v.shape[-1]
    qk, vv, gates, states = _specs(chunk, d_k, d_v, lambda t: t)
    o, s = pl.pallas_call(
        _fwd_kernel, grid=(bh, n),
        in_specs=[qk, qk, vv, gates], out_specs=[vv, states],
        out_shape=[jax.ShapeDtypeStruct((bh, rows, d_v), v.dtype),
                   jax.ShapeDtypeStruct((bh, n, d_k, d_v), _SAVED)],
        scratch_shapes=[pltpu.VMEM((d_k, d_v), _CARRIED)],
        compiler_params=_params(), interpret=interpret,
        name="delta_rule_fwd")(*ops)
    return _heads_last(o, k), s


@functools.partial(jax.jit, static_argnums=(7, 8))
def _bwd_pallas(q, k, v, g, beta, states, do, chunk, interpret):
    n, ops = _operands(q, k, v, g, beta, chunk)
    (bh, rows, d_k), d_v = ops[0].shape, v.shape[-1]
    qk, vv, gates, s_spec = _specs(chunk, d_k, d_v, lambda t: n - 1 - t)
    dq, dk, dv, dgates = pl.pallas_call(
        _bwd_kernel, grid=(bh, n),
        in_specs=[qk, qk, vv, gates, s_spec, vv],
        out_specs=[qk, qk, vv, gates],
        out_shape=[jax.ShapeDtypeStruct((bh, rows, d_k), q.dtype),
                   jax.ShapeDtypeStruct((bh, rows, d_k), k.dtype),
                   jax.ShapeDtypeStruct((bh, rows, d_v), v.dtype),
                   jax.ShapeDtypeStruct((bh, n, 8, chunk), _F32)],
        scratch_shapes=[pltpu.VMEM((d_k, d_v), _CARRIED)],
        compiler_params=_params(), interpret=interpret,
        name="delta_rule_bwd")(*ops, states, _heads_first(do, rows))
    # c is the running sum of g inside a chunk: g's cotangent is c's
    # summed from each row to the chunk's end
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgates[:, :, 0], -1), -1), -1)
    rows_of = lambda x, like: _heads_last(
        x.reshape(bh, rows), like).astype(like.dtype)
    return (_heads_last(dq, k), _heads_last(dk, k), _heads_last(dv, k),
            rows_of(dg, g), rows_of(dgates[:, :, 1], beta))


def _named(o, states, keep):
    """The forward kernel's two results under the names a recompute
    region keeps them by, where its plan admitted them (`keep`): named
    HERE, before they go into the primal result and the residuals, so
    that both are the named values (``flash_attention._named`` says
    why)."""
    if not keep:
        return o, states
    return checkpoint_name(o, DELTA_OUT), checkpoint_name(states,
                                                          DELTA_STATES)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule(q, k, v, g, beta, chunk, interpret, keep):
    return _fwd_pallas(q, k, v, g, beta, chunk, interpret)[0]


def _rule_fwd(q, k, v, g, beta, chunk, interpret, keep):
    o, states = _named(*_fwd_pallas(q, k, v, g, beta, chunk, interpret),
                       keep)
    return o, (q, k, v, g, beta, states)


def _rule_bwd(chunk, interpret, keep, res, do):
    return _bwd_pallas(*res, do, chunk, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _resolve_path(d_k, d_v, on_tpu, force=None):
    """ "pallas" / "interpret" / "chunked" / "steps": with no `force`,
    the kernels on a TPU where a head's state and its cotangent fit
    VMEM beside a chunk's blocks (keys of at most 128, values of at
    most 256), the jax.numpy chunk walk anywhere else."""
    fits = d_k <= _LANES and d_v <= 2 * _LANES
    if force is None:
        return "pallas" if fits and on_tpu else "chunked"
    if force not in ("pallas", "interpret", "chunked", "steps"):
        raise ValueError(
            "gated_delta_rule: force is None, \"pallas\", \"interpret\", "
            "\"chunked\" or \"steps\", got %r" % (force,))
    if force in ("pallas", "interpret") and not fits:
        raise ValueError(
            "gated_delta_rule: the kernels hold keys of at most 128 and "
            "values of at most 256 a head, got %d and %d" % (d_k, d_v))
    return force


def _chunk_of(path, chunk=None):
    """The rows of a chunk on `path`: `chunk` where one is given, else
    the path's own."""
    if path == "steps":
        return 0
    return int(chunk or (CHUNK if path == "chunked" else KERNEL_CHUNK))


def kept_by_a_region(tokens, heads, d_k, d_v, itemsize, chunk=None):
    """What a recompute region holds and spares by keeping the op under
    `DELTA_OUT`, for ``control_flow._plan_kept``, from the shape alone:
    (bytes kept, FLOPs spared in bf16 passes, bytes of HBM traffic
    spared). Under the kernels the result AND the chunks' starting
    states are kept, all that the backward kernel reads of the forward,
    so the whole forward kernel is spared; under the jax.numpy walk the
    result alone, which spares the two output products and the chunk
    states they read: the walk itself runs again, for autodiff's
    backward reads its states."""
    path = _resolve_path(d_k, d_v, _on_tpu(None))   # as the lowering will
    c = _chunk_of(path, chunk)
    out = tokens * heads * d_v
    if path == "chunked":
        return (out * itemsize, 12 * out * (d_k + c),
                4 * out * d_k // c + out * itemsize)
    states = 4 * -(-tokens // c) * heads * d_k * d_v
    doublings = max(c.bit_length() - 2, 0)      # two products each
    f32 = 2 * c * c * (2 * doublings * c + d_k + 2 * d_v) \
        + 3 * 2 * c * d_k * d_v
    flops = -(-tokens // c) * heads * (6 * f32 + 2 * 2 * c * c * d_k)
    return (out * itemsize + states, flops,
            2 * tokens * heads * (2 * d_k + d_v) * itemsize
            + 2 * out * itemsize + states)


def gated_delta_rule(q, k, v, g, beta, chunk=None, force=None, keep=False):
    """``o`` [B, T, H, d_v] of the recurrence in the module's docstring:
    q and k [B, T, H, d_k], v [B, T, H, d_v], g and beta [B, T, H].
    Differentiable in all five. ``chunk``: the walk's rows (None: the
    path's own, ``CHUNK`` or ``KERNEL_CHUNK``); ``force``, for tests and
    probes: None (`_resolve_path`), ``"pallas"``, ``"interpret"`` (the
    kernels on the CPU), ``"chunked"`` or ``"steps"``; ``keep``: name
    what a recompute region keeps of the op (`DELTA_OUT`, and under the
    kernels `DELTA_STATES`)."""
    if q.shape != k.shape or v.shape[:3] != k.shape[:3] \
            or g.shape != k.shape[:3] or beta.shape != g.shape:
        raise ValueError(
            "gated_delta_rule: q and k [B, T, H, d_k], v [B, T, H, d_v], "
            "g and beta [B, T, H], got %s" % (
                [tuple(x.shape) for x in (q, k, v, g, beta)],))
    path = _resolve_path(k.shape[-1], v.shape[-1], _on_tpu(k), force)
    kernels = path in ("pallas", "interpret")
    chunk = _chunk_of(path, chunk)
    if kernels and chunk % 16:
        raise ValueError("gated_delta_rule: the kernels' chunk is a "
                         "multiple of 16 rows, got %d" % chunk)
    _LOWERINGS.inc(path=path, chunk=str(chunk), heads=str(k.shape[2]),
                   d_k=str(k.shape[3]), d_v=str(v.shape[3]))
    if kernels:
        return _rule(q, k, v, g, beta, chunk, path == "interpret",
                     bool(keep))
    o = delta_rule_steps(q, k, v, g, beta) if path == "steps" \
        else delta_rule_chunked(q, k, v, g, beta, chunk)
    return checkpoint_name(o, DELTA_OUT) if keep else o


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
    -> Out [B, T, H * d_v]; attrs n_head and chunk (0: the path's
    own). The path is `_resolve_path`'s, from the platform and the
    shape; a recompute region whose plan admitted the op's result has
    it named inside (`keep`)."""
    h = int(op.attr("n_head"))
    v = ctx.in1(op, "V")
    out = gated_delta_rule(
        _heads(ctx.in1(op, "Q"), h), _heads(ctx.in1(op, "K"), h),
        _heads(v, h), ctx.in1(op, "G"), ctx.in1(op, "Beta"),
        chunk=int(op.attr("chunk", 0)) or None,
        keep=id(op) in ctx.kept_ops)
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


# pallas imports at the end, as ``flash_attention.py`` has them: a
# CPU-only environment that never takes the kernels still imports this
from jax.experimental import pallas as pl                    # noqa: E402
from jax.experimental.pallas import tpu as pltpu             # noqa: E402
