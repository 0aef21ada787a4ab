"""Control-flow ops: recurrent (scan), while, conditional_block.

Reference parity: operators/recurrent_op.cc:53-310 (step scopes + ex-state
linkage), while_op.cc, conditional_block_op.cc.

TPU-first: the reference runs sub-blocks with a per-step Scope tree and
hand-written gradient ops. Here a sub-block is traced into a step function
and driven by ``lax.scan`` / ``lax.while_loop`` / ``lax.cond`` — XLA compiles
one fused loop body, and reverse-mode autodiff of scan replaces the
reference's RecurrentGradOp entirely. Variable-length sequences use masking
(carry holds the last real state once a sequence ends), the static-shape
equivalent of shrink_rnn_memory.
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..core import registry
from ..monitor import metrics as _metrics
from .common import I64
from .flash_attention import KEPT_IN_REGIONS
from ..core.registry import register, LowerContext


def _trace_block(ctx, block, env):
    from ..core.executor import _lower_op
    sctx = LowerContext(env, ctx._rng_fn, is_test=ctx.is_test,
                        executor=ctx.executor, block=block,
                        static_info=ctx.static_info,
                        fetch_names=getattr(ctx, "fetch_names", ()))
    for op2 in block.ops:
        _lower_op(sctx, op2)
    return env


@register("recurrent")
def _recurrent(ctx, op):
    """Scan a sub-block over the time axis.

    inputs:  "inputs" outer sequence vars; "initial_states" state boot vars;
             optional "sequence_length" lengths [B]
    outputs: "outputs" stacked step outputs; "final_states"
    attrs:   sub_block, inner_input_names, inner_state_names,
             inner_state_out_names, inner_output_names, time_major, reverse
    """
    block = op.attr("sub_block")
    inner_inputs = op.attr("inner_input_names") or []
    inner_states = op.attr("inner_state_names") or []
    inner_state_outs = op.attr("inner_state_out_names") or []
    inner_outputs = op.attr("inner_output_names") or []
    time_major = op.attr("time_major", True)
    reverse = op.attr("reverse", False)

    xs = [ctx.get(n) for n in op.input("inputs")]
    if not time_major:
        xs = [jnp.moveaxis(x, 1, 0) for x in xs]           # → [T, B, ...]
    t_len = xs[0].shape[0] if xs else int(op.attr("max_len"))
    init = tuple(ctx.get(n) for n in op.input("initial_states"))

    lens = None
    if op.input("sequence_length"):
        lens = ctx.get(op.input("sequence_length")[0]).reshape(-1)

    base_env = dict(ctx.env)

    def step(carry, scanned):
        t_idx, xt = scanned
        env = dict(base_env)
        for name, v in zip(inner_states, carry):
            env[name] = v
        for name, v in zip(inner_inputs, xt):
            env[name] = v
        _trace_block(ctx, block, env)
        new_carry = tuple(env[n] for n in inner_state_outs)
        if lens is not None:
            # masked update: finished sequences keep their last state
            # (inputs are end-padded, so real steps are t < len in both
            # scan directions)
            alive = (t_idx < lens)
            new_carry = tuple(
                jnp.where(alive.reshape((-1,) + (1,) * (nc.ndim - 1)), nc, c)
                for nc, c in zip(new_carry, carry))
        outs = tuple(env[n] for n in inner_outputs)
        if lens is not None:
            alive = (t_idx < lens)
            outs = tuple(
                jnp.where(alive.reshape((-1,) + (1,) * (o.ndim - 1)), o,
                          jnp.zeros_like(o)) for o in outs)
        return new_carry, outs

    tidx = jnp.arange(t_len)
    final, ys = lax.scan(step, init, (tidx, tuple(xs)), reverse=reverse)

    for name, y in zip(op.output("outputs"), ys):
        ctx.env[name] = y if time_major else jnp.moveaxis(y, 0, 1)
    for name, s in zip(op.output("final_states"), final):
        ctx.env[name] = s


@register("while")
def _while(ctx, op):
    """Run sub-block until the condition var is false (while_op.cc).

    Carried vars are the block's written-and-read outer vars, listed in attr
    ``carry_names``. Non-differentiable (lax.while_loop); RNN-style training
    loops lower through ``recurrent`` instead, like the reference's
    DynamicRNN lowers through RecurrentOp step scopes.
    """
    block = op.attr("sub_block")
    cond_name = op.input("Condition")[0]
    carry_names = list(op.attr("carry_names") or [])
    max_iters = op.attr("max_iters")  # optional safety bound

    base_env = dict(ctx.env)
    init = tuple(ctx.get(n) for n in carry_names) + \
        (ctx.get(cond_name).reshape(()), jnp.asarray(0, jnp.int32))

    def cond_fn(carry):
        ok = carry[-2].astype(bool)
        if max_iters:
            ok = jnp.logical_and(ok, carry[-1] < max_iters)
        return ok

    def body_fn(carry):
        env = dict(base_env)
        for name, v in zip(carry_names, carry[:-2]):
            env[name] = v
        _trace_block(ctx, block, env)
        new = tuple(env[n] for n in carry_names)
        return new + (env[cond_name].reshape(()).astype(init[-2].dtype),
                      carry[-1] + 1)

    final = lax.while_loop(cond_fn, body_fn, init)
    for name, v in zip(carry_names, final[:-2]):
        ctx.env[name] = v
    ctx.env[cond_name] = final[-2]


@register("conditional_block")
def _conditional_block(ctx, op):
    """Trace the sub-block under lax.cond on a scalar condition
    (conditional_block_op.cc). Vars written by the block must pre-exist in
    env (else-branch passes them through unchanged)."""
    block = op.attr("sub_block")
    cond = ctx.get(op.input("Condition")[0]).reshape(())
    out_names = list(op.attr("written_names") or op.output("Out") or [])
    base_env = dict(ctx.env)

    missing = [n for n in out_names if n not in base_env]
    if missing:
        raise ValueError(
            "conditional_block outputs %s have no pre-set value for the "
            "false branch; assign defaults before the block" % missing)

    def true_fn(vals):
        env = dict(base_env)
        _trace_block(ctx, block, env)
        return tuple(env[n] for n in out_names)

    def false_fn(vals):
        return vals

    init = tuple(base_env[n] for n in out_names)
    outs = lax.cond(cond.astype(bool), true_fn, false_fn, init)
    for n, v in zip(out_names, outs):
        ctx.env[n] = v


_REG = _metrics.registry()
_REGIONS = _REG.counter(
    "ptpu_recompute_regions_total",
    "recompute regions lowered (one a lowering of the op, none a step)")
_KEPT_BYTES = _REG.counter(
    "ptpu_recompute_kept_bytes_total",
    "bytes a step that recompute regions keep from their forward to "
    "their backward in place of recomputing them, added where a "
    "region's gradient is traced from the shape and dtype of each value "
    "its policy saves, by the value's name (flash_out, flash_lse: a "
    "flash forward kernel's results, ops/flash_attention.py); a region "
    "with no such value in it, or one that is never differentiated, "
    "adds nothing",
    ("name",))
_KEEPS = jax.checkpoint_policies.save_only_these_names(*KEPT_IN_REGIONS)


def _region_policy(prim, *avals, **params):
    """What a recompute region saves: the values named in
    flash_attention.KEPT_IN_REGIONS and nothing else. JAX asks once for
    every equation of a region whose gradient it traces, which is where
    the kept bytes are counted."""
    keeps = _KEEPS(prim, *avals, **params)
    if keeps:
        _KEPT_BYTES.inc(sum(a.size * a.dtype.itemsize for a in avals),
                        name=params["name"])
    return keeps


@register("recompute_block")
def _recompute_block(ctx, op):
    """Rematerialization region: lower the sub-block under jax.checkpoint
    so its INTERNAL activations are recomputed during the backward pass
    instead of stored — the TPU realization of the reference era's
    memory-optimization capability (memory_optimization_transpiler.py),
    done by the AD system rather than liveness analysis. Grads flow
    through the region; RNG-consuming ops (dropout) reuse one region key,
    so the recompute replays identical masks.

    ONE thing inside a region is kept and not recomputed: the output and
    the log-sum-exp rows of a flash forward kernel (_region_policy), all
    that the flash backward reads of it, at B x T x H*Dv x 2 bytes (bf16)
    a call. So the kernel runs once a layer, and its q, k and v are still
    recomputed with the projections that make them. A region with no
    flash kernel in it (the dense path, plain layers) keeps nothing and
    lowers as under a bare jax.checkpoint.

    Outputs exported from the region are the sub-block writes consumed
    by LATER ops of the parent block (looking through their sub-blocks),
    persistables, and anything in the run's fetch list — an explicitly
    fetched region value is materialized (the user asked to store it);
    everything else is recomputed."""
    from ..core.executor import _lower_op, _NANGUARD

    block = op.attr("sub_block")
    parent_ops = list(ctx.block.ops) if ctx.block is not None else []
    try:
        my_idx = next(i for i, o in enumerate(parent_ops) if o is op)
    except StopIteration:
        raise RuntimeError(
            "recompute_block op not found in its parent block's op list "
            "— the lowering must run on the block that owns the op")
    # names a later op may read: its declared inputs PLUS everything read
    # inside any sub-block it carries (While/recurrent/IfElse bodies do
    # not re-declare their body reads as parent-op inputs)
    def op_reads(o, seen=None):
        seen = set() if seen is None else seen
        names = {n for ns in o.inputs.values() for n in ns}
        for a in o.attrs.values():
            blocks = a if isinstance(a, (list, tuple)) else [a]
            for b in blocks:
                if hasattr(b, "ops") and id(b) not in seen:
                    seen.add(id(b))
                    for o2 in b.ops:
                        names |= op_reads(o2, seen)
        return names

    later_reads = set()
    for o in parent_ops[my_idx + 1:]:
        later_reads |= op_reads(o)
    persistable = {v.name for v in ctx.block.vars.values()
                   if getattr(v, "persistable", False)} \
        if ctx.block is not None else set()
    fetches = set(getattr(ctx, "fetch_names", ()))
    out_names = [n for n in op.output("Out")
                 if n in later_reads or n in persistable or n in fetches]
    in_names = [n for n in op.input("X") if n in ctx.env]

    base_env = dict(ctx.env)
    region_key = ctx._rng_fn()
    guard_start = getattr(ctx, "_nan_idx", 0)
    op_seq = getattr(ctx, "_op_seq", 0)
    ctx._op_seq = op_seq + len(block.ops)

    def f(vals, key):
        env = dict(base_env)
        env.update(zip(in_names, vals))
        counter = [0]

        def rfn():
            counter[0] += 1
            return jax.random.fold_in(key, counter[0])

        sctx = LowerContext(env, rfn, is_test=ctx.is_test,
                            executor=ctx.executor, block=block,
                            mesh=ctx.mesh, static_info=ctx.static_info,
                            fetch_names=getattr(ctx, "fetch_names", ()))
        sctx.check_nan = getattr(ctx, "check_nan", False)
        sctx._nan_idx = guard_start   # program-order guard keys continue
        sctx._op_seq = op_seq         # and so do the ops' scope numbers
        for op2 in block.ops:
            _lower_op(sctx, op2)
        # exports: region outputs + their @LOD lengths (sequence ops
        # inside the region may have changed them) + per-op NaN guards
        # (the every-op-output contract holds inside regions too)
        lods = {n + "@LOD": env[n + "@LOD"] for n in out_names
                if env.get(n + "@LOD") is not None}
        guards = {k: v for k, v in env.items()
                  if k.startswith(_NANGUARD) and k not in base_env}
        return tuple(env[n] for n in out_names), lods, guards

    _REGIONS.inc()
    outs, lods, guards = jax.checkpoint(f, policy=_region_policy)(
        tuple(ctx.env[n] for n in in_names), region_key)
    for n, v in zip(out_names, outs):
        ctx.env[n] = v
    ctx.env.update(lods)
    ctx.env.update(guards)
    ctx._nan_idx = guard_start + len(guards)


@register("select_rows_by_mask")
def _select_rows_by_mask(ctx, op):
    """Row-wise merge for IfElse (the static-shape replacement for the
    reference's split_lod_tensor/merge_lod_tensor row partitioning): output
    rows come from TrueOut where mask else FalseOut."""
    mask = ctx.in1(op, "Mask").reshape(-1).astype(bool)
    t = ctx.in1(op, "TrueOut")
    f = ctx.in1(op, "FalseOut")
    m = mask.reshape((-1,) + (1,) * (t.ndim - 1))
    ctx.set_out(op, "Out", jnp.where(m, t, f))


# -- LoDTensorArray ops (tensor_array_read_write.cc, lod_array_length) -----
# Arrays are represented as a python-side list in env. Indices must be
# trace-time constants, so STANDALONE (block-0) usage is host-tier: the
# Executor routes such programs through the interpreter, where indices
# are concrete (While/StaticRNN sub-blocks supply python ints during
# their own lowering and are unaffected by the host marking).

@register("write_to_array", host=True)
def _write_to_array(ctx, op):
    arr_name = ctx.out_name(op, "Out")
    x = ctx.in1(op, "X")
    lst = ctx.env.get(arr_name + "@ARRAY")
    if lst is None:
        lst = []
    i = ctx.in1(op, "I")
    idx = int(jax.core.concrete_or_error(
        None, i.reshape(()), "write_to_array index must be trace-time known"))
    lst = list(lst)
    if idx == len(lst):
        lst.append(x)
    else:
        while len(lst) <= idx:
            lst.append(jnp.zeros_like(x))
        lst[idx] = x
    ctx.env[arr_name + "@ARRAY"] = lst
    # stacking is deferred to readers/fetch (_fetch_from_env) — stacking on
    # every write would be O(n^2) in trace size
    ctx.env[arr_name] = lst


@register("read_from_array", host=True)
def _read_from_array(ctx, op):
    arr_name = op.input("X")[0]
    i = ctx.in1(op, "I")
    lst = ctx.env.get(arr_name + "@ARRAY")
    idx = int(jax.core.concrete_or_error(
        None, i.reshape(()), "read_from_array index must be trace-time known"))
    if lst is None:
        lst = ctx.get(arr_name)
    ctx.set_out(op, "Out", lst[idx])


@register("lod_array_length", host=True)
def _lod_array_length(ctx, op):
    arr_name = op.input("X")[0]
    lst = ctx.env.get(arr_name + "@ARRAY")
    n = len(lst) if lst is not None else ctx.get(arr_name).shape[0]
    ctx.set_out(op, "Out", jnp.asarray([n], I64()))


@register("shrink_rnn_memory")
def _shrink_rnn_memory(ctx, op):
    # Static-shape parity: masking in `recurrent` already preserves final
    # states, so shrink is an identity on the padded batch.
    ctx.set_out(op, "Out", ctx.in1(op, "X"))


@register("max_sequence_len")
def _max_sequence_len(ctx, op):
    lens = ctx.in1(op, "RankTable")
    ctx.set_out(op, "Out", jnp.max(lens).reshape(1).astype(I64()))


@register("lod_rank_table")
def _lod_rank_table(ctx, op):
    # The rank table is (seq index, length) sorted by decreasing length
    # (framework/lod_rank_table.h). Here: just the lengths vector; ops that
    # consume it (max_sequence_len) reduce over it.
    x_name = op.input("X")[0]
    lens = ctx.maybe_get(x_name + "@LOD")
    if lens is None:
        x = ctx.get(x_name)
        lens = jnp.asarray([x.shape[0]], jnp.int32)
    ctx.set_out(op, "Out", lens)
