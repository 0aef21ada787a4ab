"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

Beyond the 2018 reference (SURVEY.md §2.7: EP absent; the closest analog is
the distributed sparse lookup table). GShard-style design: top-k gating with
capacity, dispatch/combine as einsums against a one-hot dispatch tensor, and
expert weights stacked [E, ...] sharded on ``ep`` — XLA GSPMD turns the
dispatch einsum into the all-to-all over ICI, no manual comm code.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..monitor import metrics as _metrics
from ..ops import grouped_matmul, moe_rows


def top1_gating(logits, capacity, rng=None, noise_std=0.0):
    """logits [T, E] → (dispatch [T, E, C] one-hot, combine [T, E, C],
    aux_loss). Tokens beyond an expert's capacity are dropped (standard
    Switch-transformer behavior)."""
    t, e = logits.shape
    if noise_std and rng is not None:
        logits = logits + noise_std * jax.random.normal(rng, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                  # [T]
    expert_mask = jax.nn.one_hot(expert_idx, e)              # [T, E]
    # position of each token within its expert's queue
    pos_in_expert = (jnp.cumsum(expert_mask, axis=0) - 1.0) * expert_mask
    keep = (pos_in_expert < capacity) * expert_mask          # [T, E]
    pos = jnp.sum(pos_in_expert * keep, axis=-1)             # [T]
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity)  # [T, C]
    dispatch = keep[:, :, None] * pos_oh[:, None, :]         # [T, E, C]
    gate_prob = jnp.sum(probs * expert_mask, axis=-1)        # [T]
    combine = dispatch * gate_prob[:, None, None]
    # load-balancing aux loss (GShard eq. 4 / Switch aux)
    density = jnp.mean(expert_mask, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * (e ** 2) / e
    return dispatch, combine, aux


def topk_gating(logits, capacity, k=2, rng=None, noise_std=0.0):
    """GShard-style top-k gating (top-2 is the standard MoE training
    config). Combine weights are the k selected gate probabilities
    NORMALIZED to sum to 1 per token; rank-0 choices claim expert queue
    slots before rank-1 choices (GShard sec. 2.2). Tokens whose rank-r
    choice overflows the expert's capacity lose that branch (no
    renormalization after dropping, per the paper).

    logits [T, E] → (dispatch [T, E, C], combine [T, E, C], aux_loss,
    overflow_frac) where overflow_frac = dropped assignments / (T*k).
    """
    t, e = logits.shape
    if noise_std and rng is not None:
        logits = logits + noise_std * jax.random.normal(rng, logits.shape)
    # slot bookkeeping in float32 ALWAYS: a bf16 cumsum cannot represent
    # integers past 256 exactly, so positions would collide silently
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, idxs = jax.lax.top_k(probs, k)                # [T, k]
    weights = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    counts = jnp.zeros((e,), jnp.float32)    # slots CLAIMED per expert
    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    kept_total = jnp.asarray(0.0, jnp.float32)
    for r in range(k):
        mask = jax.nn.one_hot(idxs[:, r], e)                 # [T, E] f32
        pos = (jnp.cumsum(mask, axis=0) - 1.0) * mask + counts * mask
        keep = (pos < capacity) * mask                       # [T, E]
        pos_tok = jnp.sum(pos * keep, axis=-1)               # [T]
        pos_oh = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity)
        slot = keep[:, :, None] * pos_oh[:, None, :]         # [T, E, C]
        dispatch = dispatch + slot
        combine = combine + slot * weights[:, r][:, None, None]
        # offset the next rank by slots actually CLAIMED (≤ capacity).
        # Equivalent gating to the raw-count offset — once an expert
        # overflows it is full under either bookkeeping — but counts
        # stays a true slot count.
        counts = counts + jnp.sum(keep, axis=0)
        kept_total = kept_total + jnp.sum(keep)
    overflow = jnp.clip(1.0 - kept_total / (t * k), 0.0, 1.0)
    # load-balancing aux loss on the rank-0 assignment (GShard eq. 4)
    density = jnp.mean(jax.nn.one_hot(idxs[:, 0], e), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * (e ** 2) / e
    dtype = logits.dtype
    return dispatch.astype(dtype), combine.astype(dtype), aux, overflow


def moe_ffn(x, gate_w, w_up, w_down, capacity_factor=1.25, rng=None,
            mesh=None, ep_axis="ep", top_k=1, return_stats=False):
    """Switch-style (top_k=1) or GShard-style (top_k=2) MoE FFN.

    x       [T, D] tokens
    gate_w  [D, E]
    w_up    [E, D, H] stacked expert weights (shard on ep)
    w_down  [E, H, D]
    Returns ([T, D], aux_loss), plus a stats dict ({"overflow": frac of
    dropped token-expert assignments}) when return_stats=True.
    """
    t, d = x.shape
    e = gate_w.shape[1]
    capacity = max(1, int(capacity_factor * top_k * t / e))
    logits = x @ gate_w
    if top_k > 1:
        dispatch, combine, aux, overflow = topk_gating(
            logits, capacity, k=top_k, rng=rng)
    else:
        dispatch, combine, aux = top1_gating(logits, capacity, rng)
        overflow = jnp.clip(1.0 - jnp.sum(dispatch) / t, 0.0, 1.0)
    # dispatch tokens to experts: [E, C, D]
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    if mesh is not None and ep_axis in mesh.axis_names:
        from jax.sharding import NamedSharding, PartitionSpec as P
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(ep_axis)))
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", expert_in, w_up))
    expert_out = jnp.einsum("ech,ehd->ecd", h, w_down)
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    if return_stats:
        return out, aux, {"overflow": overflow}
    return out, aux


def moe_ffn_pp_sharded(x, gate_w, w_up_local, w_down_local, ep_axis,
                       top_k=1, capacity_factor=1.25):
    """Per-DEVICE MoE FFN for use INSIDE shard_map — the pp x ep
    composition (the MoE all-to-all nested in the pipeline stage body).

    x             [T_loc, D]: THIS member's token slice (the stage
                  activations arrive batch-sharded over dp x ep)
    gate_w        [D, E] replicated (routing needs every expert's logit)
    w_up_local    [E/n_ep, D, H]: this member's expert shard (expert e's
                  owner is e // e_loc — the contiguous ep sharding of the
                  stacked [E, ...] weights)
    w_down_local  [E/n_ep, H, D]

    Routing is LOCAL (each member gates its own tokens with capacity
    cf*k*T_loc/E — the standard local-routing MoE deployment); the
    dispatched token queues ride ONE tiled lax.all_to_all to the expert
    owners ([E, C, D] -> [E/n, n*C, D]), the expert FFN runs on the
    local expert shard, and a second all_to_all brings the outputs back.
    Math per member is EXACTLY moe_ffn(mesh=None) on its token group, so
    a dense fallback that gates the same groups reproduces this bit-for-
    float (ops/parallel_ops pipeline_stack moe_gate_groups contract).

    Returns ([T_loc, D], aux_loss_local).
    """
    t, d = x.shape
    n_ep = lax.psum(1, ep_axis)
    e_loc = w_up_local.shape[0]
    e = e_loc * n_ep
    capacity = max(1, int(capacity_factor * top_k * t / e))
    logits = x @ gate_w
    if top_k > 1:
        dispatch, combine, aux, _ = topk_gating(logits, capacity, k=top_k)
    else:
        dispatch, combine, aux = top1_gating(logits, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)       # [E, C, D]
    # chunk j of the E axis (this member's queues for owner j's experts)
    # goes to member j; received chunks concatenate on the slot axis:
    # [E, C, D] -> [E/n, n*C, D] (slot block i = tokens from member i)
    expert_in = lax.all_to_all(expert_in, ep_axis, split_axis=0,
                               concat_axis=1, tiled=True)
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", expert_in, w_up_local))
    expert_out = jnp.einsum("ech,ehd->ecd", h, w_down_local)
    # inverse movement: slot block i returns to member i, rebuilding the
    # full [E, C, D] expert-major layout for the local combine
    expert_out = lax.all_to_all(expert_out, ep_axis, split_axis=1,
                                concat_axis=0, tiled=True)
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out, aux


# --------------------------------------------------------------------------
# A dropless expert layer that is told which experts it holds (ISSUE 32).
#
# One chip of an expert-parallel group routes its rows over ALL the
# experts, computes what the experts it holds give, and leaves the rest
# out: nothing here stands in for the other chips or the exchange with
# them. No token is dropped whatever the routing: the (row, expert) pairs
# that fall on held experts are sorted by expert and go through grouped
# matmuls (`_grouped`: on a TPU the Pallas kernels of
# ops/grouped_matmul.py, `grouped_matmul_rows` / `_rows_t` / `_by_expert`
# in row tiles of 256, ISSUE 63: ahead of XLA's `ragged-dot` kernels at
# every routed cell's shape, 256 to 4,096 rows an expert, so those are
# off the TPU path; `lax.ragged_dot` on the CPU and where a width is no
# whole lane tiles; either walks the tiles that hold rows and no others)
# in CHUNKS of twice the pairs
# uniform routing expects; a loop runs as many chunks as hold pairs, one
# as a rule, all of them when every row chooses held experts. So the
# matmuls' work follows the rows present, and memory a chunk, not the
# worst case of N * top_k pairs. A chunk's rows are gathered from x and
# go back to their tokens by one scatter-add: a Pallas kernel that
# visits only the places of the chunk that hold pairs (ops/moe_rows.py,
# ISSUE 35) where the device and the width allow, XLA's scatter-add over
# the whole chunk elsewhere; the gather is XLA's on every path.
# The backward is written out round the grouped matmuls (ISSUE 47),
# not derived from the forward: a chunk's pass is 2 of them forward
# (gate and up against their weights side by side; down) and 5 backward
# (`_swiglu_experts_bwd`), all on operands of the weights' dtype with
# float32 sums. It gathers a chunk's rows of x and of the cotangent as
# they are (no mask, no float32 copy: the grouped matmuls leave the
# places past the pairs out, the row kernel stops at the count, and the
# one [cap]-long mask is on the pairs' weights' gradient), recomputes
# the hidden activations from x instead of keeping them, so that nothing
# of a chunk's size outlives its iteration, applies the pairs' weights on
# the hidden side ([cap, f], the narrow one) and gets their gradient
# there too, so the down projection's forward is not run again and no
# float32 value of [cap, d] is made but what a kernel writes.
_REG = _metrics.registry()
_LOWERINGS = _REG.counter(
    "ptpu_moe_lowerings_total",
    "lowerings of the routed expert layer at trace time (none a step): "
    "the grouped matmul's path, the experts routed over, those held here, "
    "the experts a row takes, the router's score function (softmax, "
    "sigmoid), whether a shared expert rides beside the routed ones, "
    "what adds a chunk's rows to their tokens (pallas, interpret, xla), the "
    "experts' gate (silu, relu; relu2: the activation of an expert with "
    "no gate matrix) and whose rows the router reads (own: the "
    "experts' input; given: a tensor of its own)",
    ("path", "experts", "experts_held", "top_k", "score", "shared_expert",
     "rows", "activation", "router_input"))
_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}
# an expert of TWO matrices, ``w_down act(w_up x)``, no gate matrix
# (ISSUE 62): `w_gate` is None all the way down, the up projection's
# weights stand where gate and up stand side by side (`w_gu`), and the
# hidden units the counts call "on" are those the ReLU passes
_UNGATED = {"relu2": lambda u: jnp.square(jax.nn.relu(u))}
# ... and its hidden width runs in whole tiles of 256 columns: XLA's
# grouped-matmul kernels take the seven products of a chunk's pass at
# f 2,048 in 0.59 of their time at f 1,856, 14.5 lane tiles (8 experts
# of some 400 rows at d 2,688: 7.06 for 12.04 ms; at 1,920 they are
# slower still, 14.06; my chip run, PR 62). The weights are padded with
# zero columns and rows where they enter the layer, which the square of
# a ReLU leaves exact zeros and no count sees (`0 > 0`); autodiff's rule
# for the padding cuts the gradients back. The gated cells' widths are
# whole tiles as published (768, 1,024, 1,792) and take no padding.
# Under the kernels of ops/grouped_matmul.py (ISSUE 63) the six products
# take 2.41 ms at 1,920 columns, 15 lane tiles, and 2.41-2.44 at 2,048
# (XLA's 12.37 and 5.75; my chip run, PR 63): a tie, so the tile stays,
# and 1,856 as published is no whole lane tile, which the kernels want.
_UNGATED_TILE = 256


def hidden_width(f, gated=True):
    """The hidden width an expert of published width f computes at."""
    return f if gated else -(-f // _UNGATED_TILE) * _UNGATED_TILE
# What a `layers.recompute` region may keep of the layer, by the name
# the value carries (ops/control_flow.py: the block's plan prices each
# and the region's policy saves the names it admitted; outside a region
# a name is the identity). EXPERTS_OUT: the layer's output, named by the
# region where the plan admitted it. EXPERTS_ROUTE: the router's logits,
# choices and chosen scores and the sorted pairs (`route`'s logits,
# top_i and top_p, `order`, `ends`), all that the layer's and the
# router's backward read of the scope `route` but passes over [N, E]
# and [N, k], about N x (E + 3 k) x 4 bytes. EXPERTS_WEIGHTS: the
# held experts' weights in the dtype they compute in, gate and up side
# by side, as `_held_fwd` hands them to `_held_bwd`.
EXPERTS_OUT = "experts_out"
EXPERTS_ROUTE = "experts_route"
EXPERTS_WEIGHTS = "experts_weights"


def route(x, router_w, top_k, norm_topk, score="softmax", bias=None,
          scaling=1.0, norm_eps=0.0):
    """(scores [N, E], weights [N, k], experts [N, k]): the float32
    router. `score` over ALL experts ("softmax", or "sigmoid": each
    expert's own), the k largest, their weights divided by their sum
    (plus `norm_eps` where a model adds one) where `norm_topk`, times
    `scaling`. A selection `bias` [E] is added
    for the CHOICE alone (the k largest of score + bias, no gradient):
    the weights are the unbiased scores at the chosen."""
    # By name (EXPERTS_ROUTE), each BEFORE the ops whose backward rules
    # read it, so that a region which saves the name runs neither the
    # matmul, the top-k nor the gather of the chosen scores again: the
    # logits (the scores' backward reads its own result, which the
    # second forward makes from them in one pass over [N, E]), the
    # choice, the chosen scores.
    logits = checkpoint_name(
        jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                precision=lax.Precision.HIGHEST), EXPERTS_ROUTE)
    probs = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(
        probs if bias is None else lax.stop_gradient(probs + bias), top_k)
    top_i = checkpoint_name(top_i, EXPERTS_ROUTE)
    if bias is not None:
        top_p = jnp.take_along_axis(probs, top_i, axis=1)
    # (with no bias the top-k gives the chosen scores itself, and its
    # own backward reads its own choice: it runs again)
    top_p = checkpoint_name(top_p, EXPERTS_ROUTE)
    if norm_topk:
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        if norm_eps:        # (else lowered as it was before the epsilon)
            total = total + norm_eps
        top_p = top_p / total
    if scaling != 1.0:
        top_p = top_p * scaling
    return probs, top_p, top_i


def bias_step(bias, counts, rate):
    """The selection bias after a step that sent `counts` [E] rows to
    the experts: up by `rate` where an expert took fewer than the mean,
    down where more (auxiliary-loss-free balancing)."""
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


_RAGGED_DOT = ("xla", 0)    # `_grouped`'s `matmuls` for lax.ragged_dot


def _grouped(sizes, matmuls=_RAGGED_DOT):
    """The grouped matmuls on sorted rows, `sizes` rows to each expert
    in turn, as (rd, rd_t, by_expert): ``rd(rows [C, a], weights
    [Eh, a, b]) -> [C, b]`` computes no row past the sum of the sizes
    (`dtype`: what it writes, float32 unless told; it sums in float32
    either way); ``rd_t(rows [C, a], weights [Eh, b, a]) -> [C, b]`` is
    the same against each expert's weights transposed; and
    ``by_expert(a [C, m], b [C, n]) -> [Eh, m, n]`` float32 contracts
    each expert's own rows and no others (zeros for an expert with
    none). `matmuls` = (path, row tile), `routed_experts`' choice:
    ("pallas" | "interpret", tm) are the kernels `grouped_matmul_rows`,
    `grouped_matmul_rows_t` (it reads the weights as they lie) and
    `grouped_matmul_by_expert` of ops/grouped_matmul.py; ("xla", 0) is
    `lax.ragged_dot` (on a TPU XLA's `ragged-dot` kernels of 512-row
    tiles, which round a float32 operand to bfloat16 inside at twice
    the bytes; against transposed weights XLA lays them out again).
    Hand either the weights' dtype."""
    path, tm = matmuls
    if path != "xla":
        return grouped_matmul.grouped(sizes, tm, path)
    rd = lambda a, b, dtype=jnp.float32: lax.ragged_dot(
        a, b, sizes, preferred_element_type=dtype)
    rd_t = lambda a, b, dtype=jnp.float32: rd(a, jnp.swapaxes(b, 1, 2), dtype)
    dims = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(([0], [0]), ([], [])),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return rd, rd_t, lambda a, b: lax.ragged_dot_general(
        a, b, sizes, dims, preferred_element_type=jnp.float32)


def _gate_and_up(rd, xs, w_gu):
    """(g, u) [C, f] float32 of xs [C, d]: ONE grouped matmul against
    the gate's and the up projection's weights side by side, `w_gu`
    [Eh, d, 2f]."""
    gu = rd(xs, w_gu)
    f = gu.shape[1] // 2
    return gu[:, :f], gu[:, f:]


def _swiglu_experts(xs, w_gu, w_down, sizes, gate="silu", live=None,
                    matmuls=_RAGGED_DOT):
    """The held experts on sorted rows, forward: xs [C, d], `sizes` rows
    to each expert in turn; rows past their sum are not computed. Two
    grouped matmuls: `_gate_and_up`, then ``act(g) * u``, rounded to
    xs's dtype, against `w_down`. `gate` is the activation of the
    gate's half ("silu", "relu"). Given `live`, the rows that hold a
    pair, also how many of their hidden units the gate leaves on
    (``xs w_gate > 0``; int32): ``(y, on)``. `matmuls`: `_grouped`'s."""
    rd, _, _ = _grouped(sizes, matmuls)
    if gate in _UNGATED:
        g = rd(xs, w_gu)
        y = rd(_UNGATED[gate](g).astype(xs.dtype), w_down)
    else:
        g, u = _gate_and_up(rd, xs, w_gu)
        y = rd((_GATES[gate](g) * u).astype(xs.dtype), w_down)
    if live is None:
        return y
    there = jnp.arange(xs.shape[0], dtype=jnp.int32) < live
    return y, jnp.sum((g > 0) & there[:, None], dtype=jnp.int32)


def _swiglu_experts_bwd(xs, dy, w, w_gu, w_down, sizes, gate="silu",
                        matmuls=_RAGGED_DOT):
    """`_swiglu_experts`' backward for one chunk, written out: five
    grouped matmuls, every operand in xs's dtype (bfloat16 under AMP)
    and every sum float32. xs, dy [C, d]: the chunk's rows of x and of
    the output's cotangent AS GATHERED; w [C] float32: the pairs'
    weights, which the forward applies after the down projection. The
    places past the chunk's pairs hold other experts' rows and weights:
    they reach no row the caller reads and no weight gradient (see
    `_grouped`). Returns

      dweight  [C] float32: ``<dy, y> = <dy w_down^T, h>``, so the down
               projection's forward is not run again
      dxs      [C, d], xs's dtype
      dw_gu, dw_down  float32, the chunk's own sums

    Recomputed: g, u and ``h = act(g) * u`` (rounded as the forward
    rounds it). Rounded to xs's dtype where they enter a grouped
    matmul: ``h * w`` (the pair's weight rides on the hidden side of
    dw_down, so dy goes in as it is) and ``[dg, du]``, the cotangents
    of g and u side by side (so dxs is one product, not the sum of
    two); dxs is written in xs's dtype from its float32 sums. An
    ungated expert (`_UNGATED`) has no u: `w_gu` is its up projection
    alone, and the pass is 1 grouped matmul forward and 4 backward."""
    rd, rd_t, by_expert = _grouped(sizes, matmuls)
    # _GATES and _UNGATED stay the one definition of the activations
    if gate in _UNGATED:
        h, pull = jax.vjp(_UNGATED[gate], rd(xs, w_gu))
    else:
        h, pull = jax.vjp(lambda g, u: _GATES[gate](g) * u,
                          *_gate_and_up(rd, xs, w_gu))
    h = h.astype(xs.dtype).astype(jnp.float32)
    dh = rd_t(dy, w_down)
    w = w[:, None]
    dgu = jnp.concatenate(pull(dh * w), axis=1).astype(xs.dtype)
    return (jnp.sum(dh * h, axis=1), rd_t(dgu, w_gu, xs.dtype),
            by_expert(xs, dgu), by_expert((h * w).astype(xs.dtype), dy))


def _chunk(c, cap, order, ends, k):
    """Chunk c of the sorted pairs: (pairs [cap], their rows [cap],
    how many of the places hold a pair, the rows to each expert)."""
    pairs = lax.dynamic_slice_in_dim(order, c * cap, cap)
    inside = jnp.clip(ends - c * cap, 0, cap)
    sizes = inside - jnp.concatenate([inside[:1] * 0, inside[:-1]])
    return pairs, pairs // k, inside[-1], sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _held_experts(x, weight, w_gate, w_up, w_down, order, ends, cap, how,
                  dtype, gate, counted, matmuls):
    return _held_fwd(x, weight, w_gate, w_up, w_down, order, ends, cap,
                     how, dtype, gate, counted, matmuls)[0]


def _held_fwd(x, weight, w_gate, w_up, w_down, order, ends, cap, how, dtype,
              gate, counted, matmuls):
    """``(out, on)``. `how` adds a chunk's rows to their tokens
    ("pallas" / "interpret" / "xla", moe_rows._resolve_path) and
    `matmuls` makes the grouped matmuls (`_grouped`); the result
    is rounded to x's dtype and returned as `dtype`, the layer's. The
    places of a gathered chunk past its pairs hold other experts' rows:
    a grouped matmul computes no row past the sum of its sizes, and every
    other reader masks them or stops at `count`. `on` is None, or where
    `counted` the hidden units `gate` leaves on over all the pairs
    (`_swiglu_experts`): the loop carries it beside the output.

    The gate's and the up projection's weights are put side by side
    once a pass, `[Eh, d, 2f]` (the parameters keep their layout), and
    that is what the backward keeps of them, under the name
    EXPERTS_WEIGHTS beside `w_down` as it came (cast by the caller): a
    recompute region that saves the name makes neither again. Nothing
    of a chunk's size is kept."""
    k = weight.shape[1]
    w_gu = checkpoint_name(w_up if w_gate is None else jnp.concatenate(
        [w_gate, w_up], axis=2), EXPERTS_WEIGHTS)
    w_down = checkpoint_name(w_down, EXPERTS_WEIGHTS)

    def body(c, carry):
        out, on = carry
        pairs, rows, count, sizes = _chunk(c, cap, order, ends, k)
        y = _swiglu_experts(x[rows], w_gu, w_down, sizes, gate,
                            count if counted else None, matmuls)
        if counted:
            y, here = y
            on = on + here
        return moe_rows.scatter_add(out, x.shape, y, rows,
                                    weight.reshape(-1)[pairs], count,
                                    how), on

    out, on = lax.fori_loop(
        0, (ends[-1] + cap - 1) // cap, body,
        (moe_rows.zeros(x.shape, how), jnp.int32(0) if counted else None))
    return (moe_rows.result(out, x.shape, x.dtype, how, dtype), on), (
        x, weight, w_gu, w_down, order, ends)


def _held_bwd(cap, how, dtype, gate, counted, matmuls, res, douts):
    x, weight, w_gu, w_down, order, ends = res
    k = weight.shape[1]
    dout = douts[0].astype(x.dtype)

    def chunk(c, dx, dweight):
        """(dx and dweight with chunk c's pairs added, the chunk's own
        float32 gradients of `w_gu` and `w_down`)."""
        pairs, rows, count, sizes = _chunk(c, cap, order, ends, k)
        dw, dxs, *dws = _swiglu_experts_bwd(
            x[rows], dout[rows], weight.reshape(-1)[pairs], w_gu, w_down,
            sizes, gate, matmuls)
        # places past `count` name other experts' pairs
        there = jnp.arange(cap, dtype=jnp.int32) < count
        return (moe_rows.scatter_add(dx, x.shape, dxs, rows, None, count,
                                     how),
                dweight.at[pairs].add(jnp.where(there, dw, 0.0)), dws)

    def body(c, carry):
        dx, dweight, dws = chunk(c, *carry[:2])
        return dx, dweight, [a + b for a, b in zip(carry[2], dws)]

    # chunk 0 runs ahead of the loop, so that the weights' float32
    # gradients START as its results: a layer runs one chunk as a rule,
    # and a loop that carried them from zeros would read and write each
    # once more for it. With no pair at all chunk 0 gives exact zeros.
    dx, dweight, dws = lax.fori_loop(
        1, (ends[-1] + cap - 1) // cap, body,
        chunk(0, moe_rows.zeros(x.shape, how),
              jnp.zeros(weight.size, weight.dtype)))
    f = w_gu.shape[2] // 2
    dw_gate_up = (None, dws[0]) if gate in _UNGATED else (
        dws[0][:, :, :f], dws[0][:, :, f:])
    return (moe_rows.result(dx, x.shape, x.dtype, how),
            dweight.reshape(weight.shape),
            *(d if d is None else d.astype(w_down.dtype)
              for d in dw_gate_up + (dws[1],)),
            None, None)


_held_experts.defvjp(_held_fwd, _held_bwd)


def routed_experts(x, router_w, w_gate, w_up, w_down, num_experts,
                   first_expert=0, top_k=8, norm_topk=True, score="softmax",
                   bias=None, scaling=1.0, shared_expert=False, force=None,
                   router_x=None, activation="silu", count_gate=False,
                   norm_eps=0.0):
    """One chip's share of a mixture of gated experts, dropless.

    x [N, d]; router_w [d, E] over ALL `num_experts`; w_gate, w_up
    [Eh, d, f] and w_down [Eh, f, d]: the Eh experts held here, ids
    `first_expert` .. `first_expert` + Eh - 1. With `w_gate` None an
    expert is two matrices, ``w_down act(w_up x)``, act "relu2" (the
    square of a ReLU), and "the gate is on" reads ``w_up x > 0``.
    Returns

      out     [N, d], x's dtype: sum over a row's chosen experts THAT ARE
              HELD HERE of weight * w_down(act(w_gate x) * (w_up x)),
              act `activation` ("silu", or "relu"); what the other
              experts would add is left out
      aux     E * sum_e f_e P_e over all E (f_e the rows that chose e
              over N, constant; P_e the mean router probability)
      counts  [E] int32, the rows that chose each expert
      experts [N, k] int32, the router's choices

    and, where `count_gate`, a fifth: over the pairs on held experts,
    the hidden units whose gate is on (``w_gate x > 0``, what a ReLU
    gate passes; int32, no gradient).

    The router is float32 and reads x as it comes, or `router_x` [N, d]
    where given (a router that stands before the sublayers that make
    the experts' input: its weights' gradient then reaches `router_x`,
    the experts' x); the experts compute
    in their weights' dtype (bfloat16 under AMP), accumulating in
    float32. Every held pair is computed, also when all rows choose
    held experts. `score`, `bias`, `scaling` and `norm_eps` are `route`'s;
    `shared_expert` says that the caller runs a shared expert beside
    this layer (the counter's label: nothing here computes it, and a
    chip's share of the layer holds it once). A chunk's rows go back to
    their tokens by the kernel of `ops/moe_rows.py` on a TPU where d is
    whole lane tiles, by XLA's scatter-add elsewhere, and the grouped
    matmuls are the kernels of `ops/grouped_matmul.py` on a TPU where d
    and the hidden width are whole lane tiles, `lax.ragged_dot`
    elsewhere; `force` ("pallas" / "interpret" / "xla") is for tests
    and moves both, each as far as its kernels can take the shapes."""
    n, d = x.shape
    held = w_up.shape[0]
    if w_gate is None:
        extra = hidden_width(w_up.shape[2], gated=False) - w_up.shape[2]
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, extra)))
        w_down = jnp.pad(w_down, ((0, 0), (0, extra), (0, 0)))
    if activation not in (_UNGATED if w_gate is None else _GATES):
        raise ValueError(
            "routed_experts: a gated expert's activation is one of %s, an "
            "ungated one's (w_gate None) one of %s, got %r"
            % (sorted(_GATES), sorted(_UNGATED), activation))
    adder = moe_rows._resolve_path(x.shape, x, force)
    # a chunk: twice what uniform routing sends here, in whole tiles of
    # the grouped matmul
    pairs = n * top_k
    cap = min(-(-2 * pairs * held // num_experts // 512) * 512,
              -(-pairs // 8) * 8)
    matmuls = grouped_matmul.choose(cap, (d, w_up.shape[2]), x, force)
    _LOWERINGS.inc(path="ragged_dot" if matmuls[0] == "xla"
                   else "grouped_matmul", experts=str(num_experts),
                   experts_held=str(held), top_k=str(top_k), score=score,
                   shared_expert=str(bool(shared_expert)).lower(), rows=adder,
                   activation=activation,
                   router_input="own" if router_x is None else "given")
    # the plain softmax router is called as it always was, four
    # arguments: its callers' stand-ins (tests) have that signature
    how = {} if (score, bias, scaling) == ("softmax", None, 1.0) else {
        "score": score, "bias": bias, "scaling": scaling}
    if norm_eps:
        how["norm_eps"] = norm_eps
    # the scope holds what reads the router's input alone, the matmul,
    # the scores, the top-k and the sort: a trace tells it from the rest
    with jax.named_scope("route"):
        probs, weight, experts = route(x if router_x is None else router_x,
                                       router_w, top_k, norm_topk, **how)
        counts = jnp.sum(experts[..., None] == jnp.arange(num_experts),
                         axis=(0, 1), dtype=jnp.int32)
        aux = num_experts * jnp.sum(
            lax.stop_gradient(counts.astype(jnp.float32) / n)
            * jnp.mean(probs, axis=0))

        # the pairs on held experts, sorted by expert (stable: by row
        # within one); the others sort behind them and are never visited
        local = experts.reshape(-1) - first_expert
        key = jnp.where((local >= 0) & (local < held), local, held)
        order = checkpoint_name(
            jnp.argsort(key, stable=True).astype(jnp.int32), EXPERTS_ROUTE)
        ends = checkpoint_name(jnp.cumsum(lax.dynamic_slice_in_dim(
            counts, first_expert, held)).astype(jnp.int32), EXPERTS_ROUTE)
    order = jnp.pad(order, (0, -(-pairs // cap) * cap - pairs))
    out, on = _held_experts(x.astype(w_up.dtype), weight, w_gate, w_up,
                            w_down, order, ends, cap, adder, x.dtype,
                            activation, bool(count_gate), matmuls)
    got = out, aux, counts, experts.astype(jnp.int32)
    return got + (on,) if count_gate else got
