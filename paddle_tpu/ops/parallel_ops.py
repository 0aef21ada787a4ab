"""Framework-level SP / PP / EP ops.

These make the parallel/ subsystem reachable from the Program IR (round-1
review: "PP/SP/EP are libraries, not framework features"): a user building a
program through fluid.layers gets sequence-parallel attention, a pipelined
transformer stack, and MoE FFN as ordinary ops. Each lowering consults
ctx.mesh (set by ParallelExecutor): with the matching mesh axis present the
distributed path runs (shard_map over sp/pp, GSPMD all-to-all over ep);
without it the op falls back to the mathematically-identical dense form, so
the same Program runs single-device for tests and parity checks.

Reference note: the 2018 reference has no SP/PP/EP (SURVEY.md §2.7) — these
are beyond-reference capabilities required by the long-context/distributed
mandate; the op-level integration mirrors how ParallelExecutor made DP a
two-line change in the reference API.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..core.registry import register


def _mesh_axis(ctx, name):
    mesh = ctx.mesh
    if mesh is not None and name in mesh.axis_names \
            and mesh.shape[name] > 1:
        return mesh
    return None


def _batch_axis(mesh):
    return "dp" if (mesh is not None and "dp" in mesh.axis_names) else None


def _dense_attention(q, k, v, n_head, causal, scale, mesh=None):
    """Attention off an sp mesh, q/k/v [B, T, H*D]: the projections'
    layout, which the flash kernels read and write as it is."""
    # routes to the Pallas flash kernel on TPU (streaming softmax, no
    # [T, T] HBM materialization); dense XLA math elsewhere
    from .flash_attention import flash_bthd, heads_per_block
    fn = functools.partial(flash_bthd, causal=causal, scale=scale)
    if mesh is None or mesh.size == 1:
        return fn(q, k, v, n_head)
    # GSPMD cannot partition a Mosaic kernel (the TPU lowering refuses:
    # "wrap the call in a shard_map"). Attention is independent per
    # (batch, head), so split those dims over dp / tp by hand; each
    # device runs the kernel on its own [B/dp, T, (H/tp) D] shard: the
    # heads are a slice of the last dimension, taken over tp where a
    # device's heads are whole blocks of the kernel's.
    def axis(name, dim):
        return name if (name in mesh.axis_names
                        and dim % mesh.shape[name] == 0) else None

    tp = axis("tp", n_head // heads_per_block(n_head, q.shape[-1] // n_head))
    spec = P(axis("dp", q.shape[0]), None, tp)
    fn = functools.partial(fn, n_head=n_head // (mesh.shape[tp] if tp else 1))
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


@register("sp_attention")
def _sp_attention(ctx, op):
    """Sequence-parallel attention. Inputs Q/K/V [B, T, H*dk] with the
    attr n_head (what a projection leaves: no op moves a head on the way
    in or out), or [B, H, T, dk]: the op observes the rank, and the
    lowering makes the layout the path it takes wants (the flash kernels
    [B, T, H*dk]; ring.py, which shards T on the mesh's sp axis when
    present, [B, H, T, dk]). attrs: causal, variant ("ring" |
    "ulysses"). Dense-math-identical fallback off-mesh."""
    from .flash_attention import heads_first, heads_last
    q = ctx.in1(op, "Q")
    k = ctx.in1(op, "K")
    v = ctx.in1(op, "V")
    causal = bool(op.attr("causal", False))
    rank4 = q.ndim == 4
    n_head = q.shape[1] if rank4 else int(op.attr("n_head", 0))
    if n_head < 1 or q.shape[-1] % n_head:
        raise ValueError(
            "sp_attention: Q of rank 3 %s needs an n_head that divides "
            "its last dimension, got %d" % (q.shape, n_head))
    dk = q.shape[-1] if rank4 else q.shape[-1] // n_head
    scale = float(op.attr("scale", 0.0)) or dk ** -0.5
    mesh = _mesh_axis(ctx, "sp")
    if rank4 == (mesh is None):      # not the layout this path wants
        turn = heads_last if rank4 else functools.partial(heads_first,
                                                          n_head=n_head)
        q, k, v = turn(q), turn(k), turn(v)
    if mesh is None:
        out = _dense_attention(q, k, v, n_head, causal, scale,
                               mesh=ctx.mesh)
    else:
        from ..parallel import ring
        fn = (ring.ulysses_attention
              if op.attr("variant", "ring") == "ulysses"
              else ring.ring_attention)
        out = fn(q, k, v, mesh, axis_name="sp", causal=causal, scale=scale,
                 batch_axis=_batch_axis(mesh))
    if rank4 == (mesh is None):
        out = heads_first(out, n_head) if rank4 else heads_last(out)
    ctx.set_out(op, "Out", out)


@register("moe_ffn", stateful_rng=True)
def _moe_ffn(ctx, op):
    """MoE FFN: Switch top-1 (attr top_k=1) or GShard top-2 with
    normalized combine weights (top_k=2). Inputs X [B, T, D] or [T, D],
    GateW [D, E], WUp [E, D, H], WDown [E, H, D]; attrs capacity_factor,
    top_k. Outputs Out (same shape as X), AuxLoss (scalar load-balancing
    loss) and, when wired, Overflow (fraction of token-expert assignments
    dropped by capacity — the routing-health metric). Expert dim rides
    the ep mesh axis via GSPMD when present."""
    x = ctx.in1(op, "X")
    gate_w = ctx.in1(op, "GateW")
    w_up = ctx.in1(op, "WUp")
    w_down = ctx.in1(op, "WDown")
    cf = float(op.attr("capacity_factor", 1.25))
    top_k = int(op.attr("top_k", 1))
    from ..parallel import moe
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    out, aux, stats = moe.moe_ffn(
        flat, gate_w, w_up, w_down, capacity_factor=cf, top_k=top_k,
        mesh=ctx.mesh if _mesh_axis(ctx, "ep") else None,
        return_stats=True)
    ctx.set_out(op, "Out", out.reshape(shape))
    ctx.set_out(op, "AuxLoss", aux)
    if op.output("Overflow"):
        ctx.set_out(op, "Overflow", stats["overflow"])


@register("routed_experts")
def _routed_experts(ctx, op):
    """One chip's share of a mixture of gated experts, dropless
    (parallel/moe.routed_experts). Inputs X [B, T, D], RouterW [D, E]
    over all E experts, WGate / WUp [Eh, D, F] and WDown [Eh, F, D] of
    the Eh experts held here, Load [E] int32 (persistable); attrs
    first_expert, top_k, norm_topk. Outputs Out (what the held experts
    give; x's dtype), AuxLoss, Indices [B, T, k] (the router's choices)
    and LoadOut = Load + the rows that chose each expert, which a
    `for_test` clone leaves alone. Under AMP the experts' matmuls take
    bfloat16 operands; the router is float32 either way. Attrs
    score_func ("softmax" / "sigmoid"), routed_scaling_factor and
    shared_expert are `moe.routed_experts`'; with a Bias [E] float32
    (persistable, no gradient) the choice is the k largest of score +
    bias, and a train run writes BiasOut = `moe.bias_step` of the
    step's own counts at `bias_update_rate` and StepsOut = Steps + 1.
    With a RouterX [B, T, D] the router reads it and not X; attr
    activation ("silu" / "relu") is the experts' gate, or with no WGate
    ("relu2") the activation of an expert of two matrices; attr norm_topk_eps
    (0 where the layer sets none) is added to the chosen weights' sum
    before they are divided by it. With a GateOn [2]
    float32 (persistable) a train run writes GateOnOut = GateOn + (the
    hidden units the gate left on, the hidden units there were) over the
    step's pairs on held experts. Steps [1] int32, where the layer keeps
    it (beside a Bias or a GateOn), counts the train runs."""
    from ..amp import maybe_bf16
    from ..parallel import moe
    x = ctx.in1(op, "X")
    shape = x.shape
    router_w = ctx.in1(op, "RouterW")
    w_gate, w_up, w_down = maybe_bf16(
        ctx.in1(op, "WGate") if op.input("WGate") else None,
        ctx.in1(op, "WUp"), ctx.in1(op, "WDown"))
    k = int(op.attr("top_k"))
    bias = ctx.in1(op, "Bias") if op.input("Bias") else None
    first = int(op.attr("first_expert", 0))
    train = not (op.attr("is_test", False) or ctx.is_test)
    count_gate = train and bool(op.input("GateOn"))
    out, aux, counts, experts, *on = moe.routed_experts(
        x.reshape(-1, shape[-1]), router_w, w_gate, w_up, w_down,
        router_w.shape[1], first_expert=first, top_k=k,
        norm_topk=bool(op.attr("norm_topk", True)),
        score=op.attr("score_func", "softmax"), bias=bias,
        scaling=float(op.attr("routed_scaling_factor", 1.0)),
        shared_expert=bool(op.attr("shared_expert", False)),
        router_x=ctx.in1(op, "RouterX").reshape(-1, shape[-1])
        if op.input("RouterX") else None,
        activation=op.attr("activation", "silu"), count_gate=count_gate,
        norm_eps=float(op.attr("norm_topk_eps", 0.0)))
    ctx.set_out(op, "Out", out.reshape(shape))
    ctx.set_out(op, "AuxLoss", aux)
    ctx.set_out(op, "Indices", experts.reshape(shape[:-1] + (k,)))
    if train:
        ctx.set_out(op, "LoadOut", ctx.in1(op, "Load") + counts)
        if count_gate:
            pairs = jnp.sum(lax.dynamic_slice_in_dim(
                counts, first, w_up.shape[0]))
            ctx.set_out(op, "GateOnOut", ctx.in1(op, "GateOn") + jnp.stack(
                [on[0], pairs * w_up.shape[2]]).astype(jnp.float32))
        if bias is not None:
            ctx.set_out(op, "BiasOut", moe.bias_step(
                bias, counts, float(op.attr("bias_update_rate", 0.0))))
        if op.input("Steps"):
            ctx.set_out(op, "StepsOut", ctx.in1(op, "Steps") + 1)


def _decoder_layer_apply(p, x, n_head):
    """One pre-LN-free (post-LN, matching models/transformer.py 'dan')
    decoder-only layer from a param dict of arrays — the tp/sp twin with
    both axes off (one copy of the math to keep in sync)."""
    return _decoder_layer_apply_tp(p, x, n_head, None, None)


def _ln_apply(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
    m = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - m) * lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _decoder_layer_apply_tp(p, x, n_head, tp_axis, sp_axis=None,
                            ep_axis=None, moe_top_k=1, moe_cf=1.25):
    """Megatron tensor-parallel twin of _decoder_layer_apply, for use
    INSIDE shard_map (the pipeline stage body): p's matrix leaves are the
    LOCAL tp shards — wq/wk/wv col-sharded [d, d/tp] (head-split), wo
    row-sharded [d/tp, d], w1 col [d, f/tp] + b1 [f/tp], w2 row [f/tp, d]
    — and each sublayer closes with ONE lax.psum over tp (the Megatron
    g-operator). LN params and b2 are replicated; b2 adds after the psum.
    With sp_axis set, activations arrive sequence-sharded [b, t/sp, d]
    and attention runs the ring schedule over that axis (the pp x sp
    composition).

    MoE FFN (the pp x ep composition): when p carries gate_w/w_up/w_down
    instead of w1..b2, the FFN is a routed expert layer and the call
    returns (out, aux_loss). With ep_axis set, w_up/w_down arrive as the
    LOCAL expert shards and dispatch rides lax.all_to_all over ep
    (parallel/moe.moe_ffn_pp_sharded); otherwise the full expert set
    runs densely on this member's tokens — the same math either way, so
    the dense fallback's group-wise routing reproduces the sharded run."""
    b, t, d = x.shape
    tp = lax.psum(1, tp_axis) if tp_axis else 1
    h_local = n_head // tp
    dk = d // n_head

    q, k, v = x @ p["wq"], x @ p["wk"], x @ p["wv"]  # [b, t, h_local dk]
    if sp_axis:
        from ..parallel.ring import _ring_attention_sharded
        from .flash_attention import heads_first, heads_last
        a = heads_last(_ring_attention_sharded(
            *(heads_first(z, h_local) for z in (q, k, v)), sp_axis, True,
            dk ** -0.5))
    else:
        a = _dense_attention(q, k, v, h_local, True, dk ** -0.5)
    part = a @ p["wo"]
    if tp_axis:
        part = lax.psum(part, tp_axis)
    x = _ln_apply(x + part, p["ln1_s"], p["ln1_b"])
    if "gate_w" in p:
        from ..parallel import moe as moe_mod
        flat = x.reshape(-1, d)
        if ep_axis:
            f, aux = moe_mod.moe_ffn_pp_sharded(
                flat, p["gate_w"], p["w_up"], p["w_down"], ep_axis,
                top_k=moe_top_k, capacity_factor=moe_cf)
        else:
            f, aux = moe_mod.moe_ffn(
                flat, p["gate_w"], p["w_up"], p["w_down"],
                capacity_factor=moe_cf, top_k=moe_top_k)
        f = f.reshape(b, t, d)
        return _ln_apply(x + f, p["ln2_s"], p["ln2_b"]), aux
    h = jax.nn.relu(x @ p["w1"] + p["b1"])
    f = h @ p["w2"]
    if tp_axis:
        f = lax.psum(f, tp_axis)
    f = f + p["b2"]
    return _ln_apply(x + f, p["ln2_s"], p["ln2_b"])


_STACK_SLOTS = ("WQ", "WK", "WV", "WO", "LN1S", "LN1B", "W1", "B1", "W2",
                "B2", "LN2S", "LN2B")
_STACK_KEYS = ("wq", "wk", "wv", "wo", "ln1_s", "ln1_b", "w1", "b1", "w2",
               "b2", "ln2_s", "ln2_b")


def _pipeline_moe_fallback(ctx, op, x, params, n_head, gate_groups,
                           moe_top_k, moe_cf):
    """Dense single-device twin of the MoE pipeline: scan the SAME M
    microbatches, and within each, vmap the layer over the same
    gate_groups contiguous token groups the sharded run splits over
    dp x ep — routing (capacities, drops, aux) is then identical to the
    pipelined execution, which is what the dryrun parity check demands.
    Attention and LN are batch-elementwise, so the group vmap changes
    nothing for them."""
    m = int(op.attr("num_microbatches", 0))
    if m < 1:
        raise ValueError(
            "pipeline_stack MoE needs an EXPLICIT num_microbatches: "
            "routing is per-microbatch, so the dense fallback can only "
            "reproduce the pipelined model if M is static")
    b = x.shape[0]
    g = max(1, gate_groups)
    if b % m or (b // m) % g:
        raise ValueError(
            "pipeline_stack MoE: batch %d must divide into %d "
            "microbatches x %d gate groups" % (b, m, g))
    layer_apply = functools.partial(
        _decoder_layer_apply_tp, n_head=n_head, tp_axis=None,
        sp_axis=None, ep_axis=None, moe_top_k=moe_top_k, moe_cf=moe_cf)
    if op.attr("recompute"):
        layer_apply = jax.checkpoint(layer_apply)
    per_group = jax.vmap(layer_apply, in_axes=(None, 0))

    def layer_body(carry, layer_p):
        xg, aux = carry
        xg2, aux_l = per_group(layer_p, xg)
        return (xg2, aux + jnp.mean(aux_l).astype(jnp.float32)), None

    def mb_body(aux_total, mb):
        rows = mb.shape[0]
        xg = mb.reshape((g, rows // g) + mb.shape[1:])
        (xg_out, aux_mb), _ = lax.scan(
            layer_body, (xg, jnp.asarray(0.0, jnp.float32)), params)
        return aux_total + aux_mb, xg_out.reshape(mb.shape)

    mbs = x.reshape((m, b // m) + x.shape[1:])
    aux_total, outs = lax.scan(
        mb_body, jnp.asarray(0.0, jnp.float32), mbs)
    ctx.set_out(op, "Out", outs.reshape(x.shape))
    if op.output("AuxLoss"):
        ctx.set_out(op, "AuxLoss", aux_total / m)


# per-leaf PartitionSpec tails (dims AFTER the leading stage/chunk dims)
# for Megatron tp sharding of the stacked decoder params: in-projections
# and w1 col-sharded, out-projections row-sharded, everything else
# replicated (b2 adds after the psum)
_TP_SPEC_TAILS = {
    "wq": (None, None, "tp"), "wk": (None, None, "tp"),
    "wv": (None, None, "tp"), "wo": (None, "tp", None),
    "w1": (None, None, "tp"), "b1": (None, "tp"),
    "w2": (None, "tp", None), "b2": (None, None),
    "ln1_s": (None, None), "ln1_b": (None, None),
    "ln2_s": (None, None), "ln2_b": (None, None),
}


@register("pipeline_stack")
def _pipeline_stack(ctx, op):
    """A stack of L identical causal decoder layers with layer-STACKED
    parameters (leading dim L). With a pp mesh axis of size S the stack
    runs as an S-stage pipeline (L/S layers per stage, activations on the
    ICI ring); otherwise as a lax.scan over layers. Attrs: n_head,
    num_microbatches (0 = auto: 2*S for gpipe, S for interleaved),
    recompute (jax.checkpoint per layer), schedule ("gpipe" |
    "interleaved" — Megatron virtual stages, bubble/V, for the small-M
    regime), virtual_stages (V chunks per device, interleaved only;
    0 = auto L/S).

    Composition: a tp mesh axis Megatron-shards every stage's weights
    (col/row) with one psum per sublayer inside the stage body; an sp
    axis shards the sequence dim and runs ring attention inside the
    stage (parallel/ring._ring_attention_sharded); GateW/WUp/WDown
    slots replace W1..B2 with a routed MoE FFN whose experts shard on
    the ep axis and whose dispatch all-to-alls INSIDE the stage body
    (pp x ep). dp shards the microbatch dim as before — and with MoE
    the token groups split over dp x ep jointly, at the STATIC
    granularity attr moe_gate_groups (= dp*ep), so the dense fallback
    reproduces the pipelined routing exactly. MoE adds the AuxLoss
    output (live-tick-masked load-balancing loss)."""
    x = ctx.in1(op, "X")
    n_head = int(op.attr("n_head", 8))
    params = {key: ctx.in1(op, slot)
              for key, slot in zip(_STACK_KEYS, _STACK_SLOTS)
              if op.input(slot)}
    moe = bool(op.input("GateW"))
    moe_top_k = int(op.attr("moe_top_k", 1))
    moe_cf = float(op.attr("moe_capacity_factor", 1.25))
    gate_groups = int(op.attr("moe_gate_groups", 1) or 1)
    if moe:
        params["gate_w"] = ctx.in1(op, "GateW")
        params["w_up"] = ctx.in1(op, "WUp")
        params["w_down"] = ctx.in1(op, "WDown")
    n_layer = params["wq"].shape[0]
    mesh = _mesh_axis(ctx, "pp")

    if mesh is None:
        if moe:
            _pipeline_moe_fallback(ctx, op, x, params, n_head,
                                   gate_groups, moe_top_k, moe_cf)
            return
        layer_apply = functools.partial(_decoder_layer_apply,
                                        n_head=n_head)
        if op.attr("recompute"):
            layer_apply = jax.checkpoint(layer_apply)

        def body(carry, layer_p):
            return layer_apply(layer_p, carry), None

        out, _ = lax.scan(body, x, params)
        ctx.set_out(op, "Out", out)
        return

    from ..parallel import pipeline
    tp_axis = "tp" if _mesh_axis(ctx, "tp") else None
    sp_axis = "sp" if _mesh_axis(ctx, "sp") else None
    ep_axis = "ep" if (moe and _mesh_axis(ctx, "ep")) else None
    if moe and sp_axis:
        raise NotImplementedError(
            "pipeline_stack MoE does not compose with sequence "
            "parallelism yet (routing granularity under a sequence "
            "shard is undefined); use pp x ep without sp")
    if moe:
        if int(op.attr("num_microbatches", 0)) < 1:
            raise ValueError(
                "pipeline_stack MoE needs an EXPLICIT num_microbatches: "
                "routing is per-microbatch, so the dense fallback can "
                "only reproduce the pipelined model if M is static")
        dp_size = mesh.shape["dp"] if "dp" in mesh.axis_names else 1
        ep_size = mesh.shape["ep"] if ep_axis else 1
        if gate_groups != dp_size * ep_size:
            raise ValueError(
                "pipeline_stack moe_gate_groups=%d does not match the "
                "mesh's dp*ep=%d*%d: the static routing granularity "
                "must equal the token-split so the dense fallback and "
                "the sharded run gate the same groups"
                % (gate_groups, dp_size, ep_size))
    if tp_axis:
        tp = mesh.shape["tp"]
        d_inner = params["w1"].shape[-1] if "w1" in params else 0
        if n_head % tp or d_inner % tp:
            raise ValueError(
                "pipeline_stack tp composition needs n_head (%d) and "
                "d_inner (%d) divisible by tp=%d" % (n_head, d_inner, tp))
    if tp_axis or sp_axis or moe:
        layer_apply = functools.partial(_decoder_layer_apply_tp,
                                        n_head=n_head, tp_axis=tp_axis,
                                        sp_axis=sp_axis, ep_axis=ep_axis,
                                        moe_top_k=moe_top_k,
                                        moe_cf=moe_cf)
    else:
        layer_apply = functools.partial(_decoder_layer_apply,
                                        n_head=n_head)
    if op.attr("recompute"):
        layer_apply = jax.checkpoint(layer_apply)

    if moe:
        def stage_fn(stage_params, mb):
            def body(carry, layer_p):
                h, aux = carry
                h2, aux_l = layer_apply(layer_p, h)
                return (h2, aux + aux_l.astype(jnp.float32)), None

            (out, aux), _ = lax.scan(
                body, (mb, jnp.asarray(0.0, jnp.float32)), stage_params)
            return out, aux
    else:
        def stage_fn(stage_params, mb):
            def body(carry, layer_p):
                return layer_apply(layer_p, carry), None

            out, _ = lax.scan(body, mb, stage_params)
            return out

    s = mesh.shape["pp"]
    schedule = str(op.attr("schedule", "") or "gpipe")
    # per-leaf spec tails (dims after the leading stage/chunk dims):
    # Megatron col/row tp shards for the dense params, expert-dim ep
    # shards for the MoE stacks (gate_w stays replicated — routing
    # needs every expert's logit)
    if tp_axis or ep_axis:
        def _tail(key, p):
            if key in ("w_up", "w_down"):
                return ((None, "ep") + (None,) * (p.ndim - 3)) \
                    if ep_axis else (None,) * (p.ndim - 1)
            if tp_axis and key in _TP_SPEC_TAILS:
                return _TP_SPEC_TAILS[key]
            return (None,) * (p.ndim - 1)

        param_specs = {k: _tail(k, p) for k, p in params.items()}
    else:
        param_specs = None
    # MoE token groups split over dp AND ep jointly (each (dp, ep)
    # member routes its own token slice — the moe_gate_groups contract)
    if moe:
        batch_axes = tuple(a for a in ("dp", "ep")
                           if a in mesh.axis_names and mesh.shape[a] > 1)
        batch_axis = batch_axes or None
    else:
        batch_axis = _batch_axis(mesh)
    b = x.shape[0]
    if schedule == "interleaved":
        v_chunks = int(op.attr("virtual_stages", 0)) or n_layer // s
        if v_chunks < 1:
            raise ValueError(
                "pipeline_stack interleaved schedule needs at least one "
                "chunk per device: %d layers < pp=%d stages"
                % (n_layer, s))
        if n_layer % (s * v_chunks):
            raise ValueError(
                "pipeline_stack: %d layers not divisible into %d stages "
                "x %d virtual chunks" % (n_layer, s, v_chunks))
        per = n_layer // (s * v_chunks)
        # device d holds global chunks {d, d+S, ...}: [L,...] ->
        # [V, S, per, ...] -> [S, V, per, ...]
        stacked = {
            k: p.reshape((v_chunks, s, per) + p.shape[1:]).swapaxes(0, 1)
            for k, p in params.items()}
        m = int(op.attr("num_microbatches", 0)) or min(s, b)
        if b % m:
            raise ValueError("pipeline_stack: batch %d not divisible by "
                             "%d microbatches" % (b, m))
        mb = x.reshape((m, b // m) + x.shape[1:])
        if moe and (b // m) % gate_groups:
            raise ValueError(
                "pipeline_stack MoE: microbatch rows %d not divisible "
                "by moe_gate_groups=%d" % (b // m, gate_groups))
        out = pipeline.gpipe_interleaved(
            stage_fn, stacked, mb, mesh, v_chunks, axis_name="pp",
            batch_axis=batch_axis, param_specs=param_specs,
            seq_axis=sp_axis, with_aux=moe)
    else:
        if n_layer % s:
            raise ValueError("pipeline_stack: %d layers not divisible by "
                             "pp=%d stages" % (n_layer, s))
        per = n_layer // s
        stacked = {k: v.reshape((s, per) + v.shape[1:])
                   for k, v in params.items()}
        m = int(op.attr("num_microbatches", 0)) or 2 * s
        if b % m:
            raise ValueError("pipeline_stack: batch %d not divisible by "
                             "%d microbatches" % (b, m))
        mb = x.reshape((m, b // m) + x.shape[1:])
        if moe and (b // m) % gate_groups:
            raise ValueError(
                "pipeline_stack MoE: microbatch rows %d not divisible "
                "by moe_gate_groups=%d" % (b // m, gate_groups))
        out = pipeline.gpipe(stage_fn, stacked, mb, mesh, axis_name="pp",
                             batch_axis=batch_axis,
                             param_specs=param_specs, seq_axis=sp_axis,
                             with_aux=moe)
    if moe:
        out, aux = out
        if op.output("AuxLoss"):
            ctx.set_out(op, "AuxLoss", aux)
    ctx.set_out(op, "Out", out.reshape(x.shape))
