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

import logging
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

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
    "flash forward kernel's results, ops/flash_attention.py; mul_out: "
    "the results of the `mul` ops that the block's plan admitted); a "
    "region with no such value in it, or one that is never "
    "differentiated, adds nothing",
    ("name",))
_MUL_PLAN = _REG.gauge(
    "ptpu_recompute_mul_plan",
    "the last plan of a block's regions (_plan_kept_muls), set where "
    "its first region is lowered: `candidates` the `mul` results a "
    "backward rule reads, `admitted` those of them that are kept, "
    "`admitted_bytes` their bytes and `budget_bytes` what they had to "
    "fit in",
    ("what",))
# the ONE name of a `mul` result that a region keeps
MUL_OUT = "mul_out"
_KEEPS = jax.checkpoint_policies.save_only_these_names(*KEPT_IN_REGIONS,
                                                       MUL_OUT)
_LOG = logging.getLogger(__name__)


def _region_policy(prim, *avals, **params):
    """What a recompute region saves: the values named in
    flash_attention.KEPT_IN_REGIONS, the `mul` results that the block's
    plan named MUL_OUT, and nothing else. JAX asks once for every
    equation of a region whose gradient it traces, which is where the
    kept bytes are counted."""
    keeps = _KEEPS(prim, *avals, **params)
    if keeps:
        _KEPT_BYTES.inc(sum(a.size * a.dtype.itemsize for a in avals),
                        name=params["name"])
    return keeps


def _op_reads(o, seen=None):
    """The names an op may read: its declared inputs PLUS everything
    read inside any sub-block it carries (While/recurrent/IfElse bodies
    do not re-declare their body reads as parent-op inputs)."""
    seen = set() if seen is None else seen
    names = set(o.input_names)
    for a in o.attrs.values():
        blocks = a if isinstance(a, (list, tuple)) else [a]
        for b in blocks:
            if hasattr(b, "ops") and id(b) not in seen:
                seen.add(id(b))
                for o2 in b.ops:
                    names |= _op_reads(o2, seen)
    return names


def _later_reads(ops, idx):
    """What the ops after ops[idx] may read."""
    return set().union(*(_op_reads(o) for o in ops[idx + 1:]))


def reached_from(ops, names):
    """The names that `names` reach through `ops` in program order:
    what an op writes is reached where it may read a reached name. A
    region's ops are walked one by one (they write the region's
    outputs under their own names), any other op that carries
    sub-blocks is taken whole. The op ledger asks it which products a
    step differentiates through (core/executor.py _OpLog)."""
    reach = set(names)

    def walk(ops):
        for o in ops:
            if o.type == "recompute_block":
                walk(o.attr("sub_block").ops)
            elif _op_reads(o) & reach:
                reach.update(o.output_names)

    walk(ops)
    return frozenset(reach)


def _device_limit(ctx):
    """The bytes the executor's device says it may hold
    (memory_stats()["bytes_limit"]), 0 where the backend gives none, as
    the CPU does: the plan then admits nothing and a region lowers as
    under PR 42."""
    place = getattr(ctx.executor, "place", None)
    if place is None:
        return 0
    return int((place.jax_device().memory_stats() or {}).get(
        "bytes_limit", 0))


# the ops whose backward rules read none of their operands: a value
# that only they read, up to the region's end, is dead in the region's
# second forward
_SUMS = ("elementwise_add", "elementwise_sub", "sum")


def _read_by_a_backward_rule(ops, idx):
    """Whether some op after ops[idx] other than an addition reads its
    result, directly or through additions: the LAST product of a
    branch (down, out_proj, wo), which goes into the stream and nowhere
    else, is not, and keeping it would save no work."""
    through = set(ops[idx].output_names)
    for o in ops[idx + 1:]:
        if _op_reads(o) & through:
            if o.type not in _SUMS:
                return True
            through.update(o.output_names)
    return False


class _Unsized(Exception):
    """A Program variable whose declared shape does not give its size."""


def _plan_kept_muls(ctx):
    """Which `mul` results the regions of ctx.block keep from their
    forward to their backward, as {id(op)}: chosen ONCE, from the
    Program's static shapes, where the block's first region is lowered
    (no second trace, no compile), by what differs between programs and
    nothing else: the widths, the rows and what the device has free.

    * candidates: every `mul` of a region whose result a backward rule
      of the region reads (_read_by_a_backward_rule), with the bytes of
      its result (rows x columns x the itemsize amp.result_dtype gives)
      and what making it again costs (2 x rows x K x columns FLOPs);
    * order: FLOPs a byte, highest first (2 K over the itemsize: the
      wide-K products first), program order among equals;
    * budget: the device's limit (_device_limit) less the step's state
      as the trace holds it (every persistable value: parameters,
      Adam's moments) less a reserve for the two moments at which a
      backward holds most. What is live at both: what the ops before
      the last region write outside regions and what the regions hand
      on (the stream between layers). Then the larger of the HEAD (what
      the ops after the last region write, and the widest of it, the
      logits, once more for its gradient) and TWICE the largest
      region's own variables (its second forward's values and their
      cotangents). A variable counts its declared bytes (a `mul`
      result what AMP makes it, a reshape nothing: it is a view), so a
      float32 variable that AMP holds in bf16 counts double; the
      gradients are not taken off besides: XLA frees one as its update
      has read it, and they come as the values they are made from go
      (PERF.md section 6, PR 48, has the reckoning beside the compiled
      peaks);
    * admit in order while the sum fits.

    No marker in the block (nothing is differentiated), no limit to
    read, or a variable whose shape does not say its size: nothing is
    admitted."""
    from ..amp import result_dtype
    block, env = ctx.block, ctx.env
    ops = list(block.ops) if block is not None else []
    marker = next((i for i, o in enumerate(ops) if o.type in (
        "backward_marker", "calc_gradient_marker")), None)
    limit = _device_limit(ctx) if marker is not None else 0
    regions = [i for i, o in enumerate(ops[:marker])
               if o.type == "recompute_block"]
    if not limit or not regions:
        return frozenset()
    # of each variable the walk has met: its Program variable (a
    # region's own live in its sub-block, which a later region's does
    # not see), its elements and its bytes
    declared, elements, nbytes = {}, {}, {}

    def var_of(blk, name):
        if name not in declared:
            declared[name] = blk._find_var_recursive(name)
        return declared[name]

    def count(name):
        if name in env:
            return env[name].size
        if name not in elements:
            raise _Unsized(name)
        return elements[name]

    def sized(blk, o):
        """Bytes of what `o` writes, by the declared shapes: a -1 is
        what it is in the first operand that has one, a reshape keeps
        its operand's elements and is a view of it."""
        written = set(o.output_names)
        for name in written:
            var = var_of(blk, name)
            if var is None or var.shape is None:
                raise _Unsized(name)
            n = math.prod(s for s in var.shape if s >= 0)
            if o.type == "reshape":
                n = count(o.input("X")[0])
            elif min(var.shape, default=0) < 0:
                like = next((v for v in (var_of(blk, r) for r in sorted(
                    _op_reads(o))) if v is not None and v.shape
                    and min(v.shape) < 0), None)
                if like is None:
                    raise _Unsized(name)
                n *= count(like.name) // math.prod(
                    s for s in like.shape if s >= 0)
            dtype = result_dtype(var.dtype) if o.type == "mul" \
                else var.dtype
            elements[name] = n
            nbytes[name] = 0 if var.persistable or o.type == "reshape" \
                else n * jnp.dtype(dtype).itemsize
        return sum(nbytes[n] for n in written)

    candidates, stream, head, widest, largest = [], 0, 0, 0, 0
    try:
        for i, o in enumerate(ops[:marker]):
            if o.type != "recompute_block":
                size = sized(block, o)
                if i < regions[-1]:
                    stream += size
                else:
                    head, widest = head + size, max(widest, size)
                continue
            sub = o.attr("sub_block")
            own = 0
            for j, m in enumerate(sub.ops):
                size = sized(sub, m)
                own += size
                if m.type == "mul" and _read_by_a_backward_rule(sub.ops, j):
                    y = var_of(sub, m.input("Y")[0])
                    yn = m.attr("y_num_col_dims", 1)
                    k = math.prod(y.shape[yn:] if m.attr(
                        "transpose_Y", False) else y.shape[:yn])
                    cost = 2 * k * elements[m.output("Out")[0]]
                    candidates.append((cost / size, size, id(m)))
            largest = max(largest, own)
            stream += sum(nbytes[n] for n in
                          set(o.output("Out")) & _later_reads(ops, i))
    except _Unsized as e:
        _LOG.info("recompute: the shape of %s does not say its size; no "
                  "mul result is kept", e)
        return frozenset()
    state = sum(env[n].size * env[n].dtype.itemsize
                for n, v in block.vars.items() if v.persistable and n in env)
    reserve = stream + max(head + widest, 2 * largest)
    budget = max(0, limit - state - reserve)
    admitted, total = set(), 0
    # (a stable sort: program order among equals)
    for _, size, op_id in sorted(candidates, key=lambda c: -c[0]):
        if total + size > budget:
            break
        admitted.add(op_id)
        total += size
    for what, value in (("candidates", len(candidates)),
                        ("admitted", len(admitted)),
                        ("admitted_bytes", total),
                        ("budget_bytes", budget)):
        _MUL_PLAN.set(value, what=what)
    _LOG.info("recompute: %d of %d mul results kept, %d bytes of a "
              "budget of %d (the device's limit %d less state %d and a "
              "reserve of %d: stream %d, head %d + %d, largest region "
              "2 x %d)", len(admitted), len(candidates), total, budget,
              limit, state, reserve, stream, head, widest, largest)
    return frozenset(admitted)


@register("recompute_block")
def _recompute_block(ctx, op):
    """Rematerialization region: lower the sub-block under jax.checkpoint
    so its INTERNAL activations are recomputed during the backward pass
    instead of stored — the TPU realization of the reference era's
    memory-optimization capability (memory_optimization_transpiler.py),
    done by the AD system rather than liveness analysis. Grads flow
    through the region; RNG-consuming ops (dropout) reuse one region key,
    so the recompute replays identical masks.

    TWO things inside a region are kept and not recomputed
    (_region_policy). The output and the log-sum-exp rows of a flash
    forward kernel, all that the flash backward reads of it, at B x T x
    H*Dv x 2 bytes (bf16) a call, so the kernel runs once a layer. And
    the results of the `mul` ops that the block's plan admitted
    (_plan_kept_muls: the products a backward rule reads, the costliest
    a byte first, while they fit what the device has free), each the
    value the next op reads, at the precision the forward made it; the
    plan is made for all the block's regions where the first is
    lowered. The norms, gates, scans and expert layers round them are
    still recomputed. A region with no flash kernel in it on a device
    that states no limit (the CPU) keeps nothing and lowers as under a
    bare jax.checkpoint.

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
    region = sum(o.type == "recompute_block" for o in parent_ops[:my_idx])
    later_reads = _later_reads(parent_ops, my_idx)
    persistable = {v.name for v in ctx.block.vars.values()
                   if getattr(v, "persistable", False)} \
        if ctx.block is not None else set()
    fetches = set(getattr(ctx, "fetch_names", ()))
    out_names = [n for n in op.output("Out")
                 if n in later_reads or n in persistable or n in fetches]
    in_names = [n for n in op.input("X") if n in ctx.env]

    # the block's plan, made where its first region is lowered
    kept = getattr(ctx, "_kept_muls", None)
    if kept is None:
        kept = ctx._kept_muls = _plan_kept_muls(ctx)

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
        # the op ledger's rows of these ops say which region they sit in
        sctx._op_log, sctx._op_region = ctx._op_log, region
        for op2 in block.ops:
            _lower_op(sctx, op2)
            if id(op2) in kept:
                out = op2.output("Out")[0]
                env[out] = checkpoint_name(env[out], MUL_OUT)
                sctx.note(kept=MUL_OUT)
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
