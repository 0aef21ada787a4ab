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

import functools
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
from .delta_rule import DELTA_OUT, DELTA_STATES, kept_by_a_region
from .loss import hard_label_rows, hard_label_rows_grad
from .math import _flatten2d, mul_rows
from .short_conv import CONV_OUT
from .ssd_scan import saved_states_bytes
from ..core.registry import register, LowerContext
from ..parallel.moe import (EXPERTS_OUT, EXPERTS_ROUTE, EXPERTS_WEIGHTS,
                            hidden_width)


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
    "flash forward kernel's results, ops/flash_attention.py; mul_out, "
    "short_conv_out, delta_rule_out, experts_out, experts_route, "
    "experts_weights: what "
    "the block's plan admitted, by kind; delta_rule_states: the chunks' "
    "starting states that the kind delta_rule_out holds beside the "
    "result under the rule's kernels); a region with no such value "
    "in it, or one that is never differentiated, adds nothing",
    ("name",))
_MUL_PLAN = _REG.gauge(
    "ptpu_recompute_mul_plan",
    "the `mul` series of ptpu_recompute_plan under its first name: "
    "`candidates`, `admitted`, `admitted_bytes` of the last plan's `mul` "
    "results, and `budget_bytes`, the room the regions before the last "
    "had to fit in",
    ("what",))
_PLAN = _REG.gauge(
    "ptpu_recompute_plan",
    "the last plan of a block's regions (_plan_kept), set where its "
    "first region is lowered, by the kind of value (the name it is kept "
    "under): `candidates` the values of the kind that a backward rule "
    "reads, `admitted` those of them that are kept and `admitted_bytes` "
    "their bytes; under kind `all` also `budget_bytes` (the room for "
    "what the regions before the last keep) and `head_budget_bytes` "
    "(the room for what all regions keep); under kind "
    "`loss_in_row_blocks`, which keeps nothing, `regions`, how many "
    "regions of the step run a head and its loss in row blocks (a "
    "block's visits counted each; set with no limit to plan by too), "
    "and `rows`, the rows of a block",
    ("kind", "what"))
_COMPILED = _REG.gauge(
    "ptpu_recompute_compiled_bytes",
    "memory_analysis() of the last compiled step whose program holds "
    "recompute regions, read at the step's first call: `argument`, "
    "`output`, `alias`, `temp`, and `limit`, the device's bytes_limit "
    "(0 where it states none)",
    ("what",))
_FALLBACKS = _REG.counter(
    "ptpu_recompute_plan_fallbacks_total",
    "steps lowered a second time with a plan of nothing because the "
    "compile with the regions' plan failed with RESOURCE_EXHAUSTED")
# the ONE name of a `mul` result that a region keeps
MUL_OUT = "mul_out"
# the plan's kind of what keeps nothing: the regions of a step that run
# a head and its loss in row blocks (`regions`, a block's visits counted
# each) and the rows of a block (`rows`)
LOSS_BLOCKS = "loss_in_row_blocks"
_LOG = logging.getLogger(__name__)
if not _LOG.handlers:
    # the plan's lines (two a build of a program with regions) are what
    # says on a chip how far a step stands from the device's limit:
    # they show with no logging set up, on the standard error
    _LOG.addHandler(logging.StreamHandler())
    _LOG.handlers[0].setFormatter(logging.Formatter("[paddle_tpu] %(message)s"))
    _LOG.setLevel(logging.INFO)

# (bf16 FLOP/s, HBM bytes/s) by device kind, for the ORDER of the
# plan's candidates (seconds to make a value again over its bytes);
# a kind that is not here is reckoned as a v5e
_RATES = {"TPU v5 lite": (197e12, 819e9), "TPU v5e": (197e12, 819e9),
          "TPU v4": (275e12, 1228e9), "TPU v5": (459e12, 2765e9),
          "TPU v5p": (459e12, 2765e9), "TPU v6 lite": (918e12, 1640e9)}


def _saves(names):
    """The policy of a region that saves the values named in `names`
    and nothing else. JAX asks once for every equation of a region
    whose gradient it traces, which is where the kept bytes are
    counted."""
    keeps = jax.checkpoint_policies.save_only_these_names(*names)

    def policy(prim, *avals, **params):
        kept = keeps(prim, *avals, **params)
        if kept:
            _KEPT_BYTES.inc(sum(a.size * a.dtype.itemsize for a in avals),
                            name=params["name"])
        return kept

    return policy


# What a recompute region saves where its plan names nothing inside an
# op's own lowering: the values named in flash_attention.KEPT_IN_REGIONS
# and the results that the block's plan named where their ops were
# lowered (MUL_OUT, CONV_OUT, EXPERTS_OUT; DELTA_OUT and DELTA_STATES,
# which the rule's own lowering gives where the plan kept the op), and
# nothing else.
# EXPERTS_ROUTE and EXPERTS_WEIGHTS are given inside the expert layer
# wherever it is lowered, and saved by the regions whose plan admitted
# them.
_IN_EVERY_REGION = KEPT_IN_REGIONS + (MUL_OUT, CONV_OUT, DELTA_OUT,
                                      DELTA_STATES, EXPERTS_OUT)
_region_policy = _saves(_IN_EVERY_REGION)


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


def _regions_in(ops):
    return sum(o.type == "recompute_block" for o in ops)


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
            elif o.type == "repeat":
                # a visit reads what the visit before handed on: twice,
                # so that what an update reaches is reached as carried
                for _ in range(2):
                    reach.update(c for c, i, u in zip(
                        o.attr("carry_names"), o.input("Init"),
                        o.attr("update_names")) if {i, u} & reach)
                    walk(o.attr("sub_block").ops)
                if set(o.attr("update_names") + o.attr("output_names")) \
                        & reach:
                    reach.update(o.output_names)
            elif _op_reads(o) & reach:
                reach.update(o.output_names)

    walk(ops)
    return frozenset(reach)


def _device(ctx):
    place = getattr(ctx.executor, "place", None)
    return None if place is None else place.jax_device()


def _device_limit(ctx):
    """The bytes the executor's device says it may hold
    (memory_stats()["bytes_limit"]), 0 where the backend gives none, as
    the CPU does: the plan then admits nothing and a region lowers as
    under PR 42."""
    device = _device(ctx)
    if device is None:
        return 0
    return int((device.memory_stats() or {}).get("bytes_limit", 0))


# the ops whose backward rules read none of their operands: a value
# that only they read, up to the region's end, is dead in the region's
# second forward (`scale`: a published multiplier on a sublayer's result
# before it joins the stream, models/granite_hybrid.py)
_SUMS = ("elementwise_add", "elementwise_sub", "sum", "scale")


def _read_by_a_backward_rule(ops, idx, slot=None):
    """Whether some op after ops[idx] other than an addition reads its
    result (its output `slot` alone where one is given), directly or
    through additions: the LAST product of a branch (down, out_proj,
    wo), which goes into the stream and nowhere else, is not, and
    keeping it would save no work."""
    through = set(ops[idx].output_names if slot is None
                  else ops[idx].output(slot))
    for o in ops[idx + 1:]:
        if _op_reads(o) & through:
            if o.type not in _SUMS:
                return True
            through.update(o.output_names)
    return False


# What a region of a block that is visited again holds at its backward,
# in times its declared values, where every other region holds twice
# them: EMPIRICAL, from `ouro_train_T8k`'s step compiled for a described
# v5e, which at two stood over the limit (_plan_kept says the readings)
_VISITED_REGION_TIMES = 3
# The scope jax.checkpoint gives a region's second forward. The product
# that a head in row blocks makes again in its backward rule carries it
# too, inside its `mul` row's scope: a trace's readers tell a second
# forward by this name, and the work is one whoever orders it
_SECOND_FORWARD = "rematted_computation"
# A region's head and loss in row blocks (_loss_in_row_blocks): the
# float32 [rows, V] block of logits that the loss works on at a time
# stays under this (1,024 rows at 49,152 columns), as
# flash_attention._RESIDENT_DQ_BYTES bounds what ONE backward kernel holds
_LOSS_BLOCK_BYTES = 256 * 2 ** 20


def _block_rows(n, columns):
    """The rows a block of a head and loss in row blocks holds, from the
    shapes alone: all n where their float32 logits stay under
    _LOSS_BLOCK_BYTES, else the largest divisor of n that does and is
    whole sublane tiles (a multiple of 8); 0 where there is none."""
    most = _LOSS_BLOCK_BYTES // (4 * columns)
    if n <= most:
        return n
    return max((r for r in range(8, most + 1, 8) if n % r == 0), default=0)


def _exports(blk, blk_ops, idx, fetched):
    """What region blk_ops[idx] hands out of itself: of its outputs
    those that a later op of its block may read, the persistable ones
    and those in `fetched` (the run's fetch list and, in a `repeat`
    block, what a visit hands on)."""
    later = _later_reads(blk_ops, idx)
    return [n for n in blk_ops[idx].output("Out")
            if n in later or n in fetched or getattr(
                blk._find_var_recursive(n), "persistable", False)]


def _head_and_loss(sub_ops, exported, shape_of):
    """What a region that is a head and its loss, and nothing else,
    gives to lower it in row blocks: ``(mul, loss, never made, K, V)``,
    the two ops, the names of the values that the block form never
    makes (the logits under each of their shapes and the op's
    `Softmax`) and the product's inner and outer width; None for every
    other region. Told from the Program alone: the region's ops are
    ONE `mul` that reads its operands from outside (`fc` without bias;
    `shape_of` says its weight's shape), ONE `softmax_with_cross_entropy`
    with `soft_label` false, and reshapes; the product's result reaches
    the op's `Logits` through reshapes only, as ``[rows, V]``; nothing
    else reads it; the op's `Softmax` is read by nobody; and none of
    them is in `exported` (_exports)."""
    kinds = [o.type for o in sub_ops]
    if kinds.count("mul") != 1 \
            or kinds.count("softmax_with_cross_entropy") != 1 \
            or not set(kinds) <= {"mul", "reshape",
                                  "softmax_with_cross_entropy"}:
        return None
    mul = sub_ops[kinds.index("mul")]
    loss = sub_ops[kinds.index("softmax_with_cross_entropy")]
    if set(mul.input_names) & set().union(*(
            o.output_names for o in sub_ops)):
        return None
    y_shape, yn = shape_of(mul.input("Y")[0]), mul.attr("y_num_col_dims", 1)
    k, v = math.prod(y_shape[:yn]), math.prod(y_shape[yn:])
    if mul.attr("transpose_Y", False):
        k, v = v, k
    logits, shape = set(mul.output("Out")), [None] * (
        mul.attr("x_num_col_dims", 1) + 1)
    for o in sub_ops[kinds.index("mul") + 1:]:
        if o.type == "reshape" and set(o.input_names) & logits:
            logits.update(o.output_names)
            if o.output("Out") == loss.input("Logits"):
                shape = list(o.attr("shape"))
    never_made = logits | set(loss.output("Softmax"))
    if loss.attr("soft_label", False) \
            or not set(loss.input("Logits")) <= logits \
            or set(loss.input("Label")) & logits \
            or len(shape) != 2 or shape[1] not in (v, None) \
            or never_made & set(exported) or any(
                set(loss.output("Softmax")) & _op_reads(o) for o in sub_ops):
        return None
    return mul, loss, frozenset(never_made), k, v


class _Unsized(Exception):
    """A Program variable whose declared shape does not give its size."""


def _plan_kept(ctx):
    """What the regions of ctx.block keep from their forward to their
    backward besides the flash kernels' results: ``(ops, names)``,
    `ops` {id(op): the name the region gives the op's result} and
    `names` {region index: the names its policy saves besides} (those
    the expert layer gives inside its lowering). Chosen ONCE, from the
    Program's static shapes, where the block's first region is lowered
    (no second trace, no compile), by what differs between programs and
    nothing else: the ops, the widths, the rows and what the device has
    free.

    * candidates, each (name, bytes kept, seconds to make it again),
      and only where a backward rule of the region reads the value
      (_read_by_a_backward_rule), seconds being FLOPs over the bf16
      peak plus bytes moved over the HBM's rate (_RATES, by the
      device's kind), from the declared shapes:
      - MUL_OUT, a `mul` result: rows x columns x the itemsize
        amp.result_dtype gives; 2 x rows x K x columns FLOPs and the
        result written once;
      - short_conv.CONV_OUT, a `gated_short_conv` result, which the
        projection after it reads for its weight's gradient: the op's
        forward bytes (X read, the result written);
      - delta_rule.DELTA_OUT, a `gated_delta_rule` result, which the
        gated norm after it reads, priced by the path the op takes
        (delta_rule.kept_by_a_region): under its kernels the result and
        the chunks' starting states (DELTA_STATES, named beside it
        inside the op's custom rule) are all the backward kernel reads
        of the forward, so the kind holds both and spares the whole
        forward kernel; under the jax.numpy walk the result alone,
        which spares the two output products and the chunk states they
        read: that walk runs again, for autodiff's backward reads its
        states;
      - moe.EXPERTS_OUT, a `routed_experts` output, ONLY where an op
        other than an addition reads it (a norm after the layer, a
        stream's merge): otherwise the second forward needs none of it
        and the layer's loop is dead there already. Cost: the layer's
        forward, two grouped matmuls over the pairs uniform routing
        sends to the held experts (over an expert's three matrices, or
        the two of one with no gate matrix), the rows gathered and
        added back;
      - moe.EXPERTS_ROUTE, what the layer's and the router's backward
        read of the scope `route` (the logits, the choice, the chosen
        scores, the sorted pairs): the router's float32 matmul, the
        top-k, the gather and the sort again;
      - moe.EXPERTS_WEIGHTS, the held experts' weights as the layer
        computes with them: read as declared, written in the compute
        dtype.
      NOT candidates: the stream's norms (XLA fuses them with their
      neighbours; a kernel there lost 2 ms in PR 33), `qk_norm_rope`,
      the hyper-connections' `h` and `zs`, the scans (`selective_scan`,
      and `ssd_scan` with the chunk states its forward kernel saves): a
      region runs them twice still.
    * order: seconds a byte, highest first (among `mul` results 2 K
      over the itemsize, as before: the wide-K products first); among
      equals the LAST region's first, then program order: the backward
      runs the regions last to first, so the last region's values are
      held the shortest.
    * room: the device's limit (_device_limit) less the step's state
      as the trace holds it (every persistable value: parameters,
      Adam's moments) less a reserve, at the two kinds of moment at
      which a step holds most. What is live at both: what the ops
      before the last region write outside regions and what the
      regions hand on (the stream between layers). (1) The HEAD: what
      the ops after the last region write, and the widest of it, the
      logits, once more for its gradient; every kept value is live
      there. (2) A region's backward: TWICE the largest region's own
      variables at their declared bytes (a `mul` result what AMP makes
      it, a reshape nothing: it is a view), which stands for the second
      forward's values, their cotangents and what an op holds inside
      (the expert layer's chunk: PERF.md section 6, PR 52, has the
      compiled working sets beside it). There a region's OWN kept
      values stand where their recomputed copies would, and the values
      of the regions after it are gone: the moment that holds most is
      the last region's backward, with what every region BEFORE it
      keeps. So a value of the last region is charged to the head
      alone, any other to both. The gradients are not taken off
      besides: XLA frees one as its update has read it (PERF.md
      section 6, PR 48).
    * visits (a ``layers.repeat`` block, whose regions the walk finds
      in the block and takes in the step's order, `times` times each):
      a VISIT is one run of the block, and a region of it keeps its
      values once a visit, so a candidate there holds `times` times the
      bytes and spares `times` times the seconds (the same seconds a
      byte: all its visits are admitted or none); what the block's
      other ops write, and what its regions hand on, is stream `times`
      times. Of the block's last region the last visit alone is "the
      last region": the visits before it are charged to both moments.
      The room is less, at both, by the float32 gradients of every
      parameter the block reads where `times` is over 1: such a
      gradient is the sum over the visits and is held from the last
      visit's backward to the first's, which the rule above ("XLA
      frees one as its update has read it") does not cover. A region
      of a block that is visited several times is reckoned at
      _VISITED_REGION_TIMES its values at its backward, not at twice.
      That three is EMPIRICAL, one cell's reading and no derivation:
      at twice, the plan of `ouro_train_T8k` admits 64 products (2.62
      GB) and the step compiled for a described v5e stands 0.85 GiB
      over the limit; beside the state, the stream and what is kept it
      holds 3.2 GB, 2.8 times a layer region's values, with two visits'
      second forwards among its largest allocations at once (a second
      forward waits on nothing but what was kept; PERF.md section 6,
      PR 60; tests/test_recompute_visits.py pins both plans'
      arithmetic). Ordering a layer region's second forward behind the
      cotangent that comes into it, as a head's rule now does
      (_rows_function), is what would let it be two again. A region
      that ENDS IN THE LOSS (it holds a cross-entropy: a visit's head)
      is a head moment of its own: its values and the widest of them
      once more, where another region is reckoned at twice its values;
      the larger of the two kinds stands at the last region's
      backward. Its logits are priced as any `mul` result. One that is
      lowered IN ROW BLOCKS (_in_row_blocks: a head, its loss and
      nothing else) never makes its logits nor its softmax: it holds,
      at its backward, its other values, the logits' gradient whole in
      the logits' dtype and, of ONE block, the logits and in float32
      their softmax and its gradient; its product is no candidate,
      there being nothing to keep; the plan counts such regions under
      the kind LOSS_BLOCKS, with a limit to plan by or with none. What
      a model builds under ``layers.forward_only`` (the last visit's
      logits, for a forward run) is not lowered in a train step and
      counts nowhere.
    * admit in order what fits at every moment it is charged to; a
      candidate that does not fit is passed over.

    No marker in the block (nothing is differentiated), no limit to
    read, a variable whose shape does not say its size, or an executor
    that has fallen back (Executor._first_compile): nothing is
    admitted."""
    from ..amp import result_dtype
    nothing = {}, {}
    block, env = ctx.block, ctx.env
    ops = list(block.ops) if block is not None else []
    marker = next((i for i, o in enumerate(ops) if o.type in (
        "backward_marker", "calc_gradient_marker")), None)
    limit = _device_limit(ctx) if marker is not None else 0
    peak, hbm = _RATES.get(getattr(_device(ctx), "device_kind", None),
                           _RATES["TPU v5e"])
    # of each variable the walk has met: its Program variable (a
    # region's own live in its sub-block, which a later region's does
    # not see), its elements and its bytes; and the `mul` results' names
    declared, elements, nbytes, products = {}, {}, {}, set()
    carried = {c: i for o in ops[:marker] if o.type == "repeat"
               for c, i in zip(o.attr("carry_names"), o.input("Init"))}

    def var_of(blk, name):
        if name not in declared:
            declared[name] = blk._find_var_recursive(name)
        return declared[name]

    def count(name):
        if name in env:
            return env[name].size
        if name in carried:       # a `repeat` block's: what it starts as
            return count(carried[name])
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
            if o.type == "mul":
                products.add(name)
            elements[name] = n
            nbytes[name] = 0 if var.persistable or o.type == "reshape" \
                else n * jnp.dtype(dtype).itemsize
            if o.type == "ssd_scan":
                # beside its result the chunks' starting states, which
                # its forward kernel saves and its backward reads
                nbytes[name] += saved_states_bytes(
                    n, var_of(blk, o.input("B")[0]).shape[-1]
                    // int(o.attr("n_group")),
                    int(o.attr("chunk", 0)) or None)
        return sum(nbytes[n] for n in written)

    def itemsize(blk, name):
        """Of a value as the trace will hold it: a `mul` result's is
        AMP's, any other's its declared one."""
        if name in env:
            return env[name].dtype.itemsize
        var = var_of(blk, name)
        return jnp.dtype(result_dtype(var.dtype) if name in products
                         else var.dtype).itemsize

    def priced(blk, sub_ops, j):
        """The candidates op j of a region gives: (name, bytes kept,
        seconds to make them again, id(op) or None for a name the
        op's own lowering gives)."""
        m = sub_ops[j]
        if m.type == "mul" and _read_by_a_backward_rule(sub_ops, j):
            out = m.output("Out")[0]
            y = var_of(blk, m.input("Y")[0])
            yn = m.attr("y_num_col_dims", 1)
            k = math.prod(y.shape[yn:] if m.attr(
                "transpose_Y", False) else y.shape[:yn])
            size = nbytes[out]
            yield MUL_OUT, size, 2 * k * elements[out] / peak \
                + size / hbm, id(m)
        elif m.type == "gated_short_conv" \
                and _read_by_a_backward_rule(sub_ops, j):
            # (the op hands on its input's dtype)
            x, out = m.input("X")[0], m.output("Out")[0]
            size = elements[out] * itemsize(blk, x)
            yield CONV_OUT, size, (
                count(x) * itemsize(blk, x) + size) / hbm, id(m)
        elif m.type == "gated_delta_rule" \
                and _read_by_a_backward_rule(sub_ops, j):
            # (the op hands on V's dtype, which a convolution hands on
            # from a `mul`: the declared one, float32, is the safe side)
            q, v, out = m.input("Q")[0], m.input("V")[0], m.output("Out")[0]
            heads = int(m.attr("n_head"))
            d_k = var_of(blk, q).shape[-1] // heads
            d_v = var_of(blk, v).shape[-1] // heads
            size, flops, moved = kept_by_a_region(
                elements[out] // (heads * d_v), heads, d_k, d_v,
                itemsize(blk, v), int(m.attr("chunk", 0)) or None)
            yield DELTA_OUT, size, flops / peak + moved / hbm, id(m)
        elif m.type == "routed_experts":
            x, out = m.input("X")[0], m.output("Out")[0]
            w = var_of(blk, m.input("WUp")[0])
            # an expert's matrices: gate, up and down, or up and down
            mats = 3 if m.input("WGate") else 2
            held, d, f = w.shape
            f = hidden_width(f, gated=mats == 3)
            experts = var_of(blk, m.input("RouterW")[0]).shape[1]
            top_k = int(m.attr("top_k"))
            n = count(x) // d
            pairs = n * top_k * held // experts
            declared_w = jnp.dtype(w.dtype).itemsize
            computed_w = jnp.dtype(result_dtype(w.dtype)).itemsize
            if _read_by_a_backward_rule(sub_ops, j, "Out"):
                size = elements[out] * itemsize(blk, x)
                yield EXPERTS_OUT, size, 2 * mats * pairs * d * f / peak + (
                    (2 * computed_w + 4) * pairs * d + 8 * n * d
                    + mats * held * d * f * computed_w) / hbm, id(m)
            # the logits, the choices with their scores, the pairs'
            # order; read again: x in float32 for the router's six
            # bf16 passes, the scores, a sort's passes over the pairs
            yield EXPERTS_ROUTE, 4 * (n * experts + 3 * n * top_k + held), \
                12 * n * d * experts / peak + (4 * n * d + 12 * n * experts
                + 8 * n * top_k * math.ceil(math.log2(max(
                    n * top_k, 2)))) / hbm, None
            yield EXPERTS_WEIGHTS, mats * held * d * f * computed_w, \
                mats * held * d * f * (declared_w + computed_w) / hbm, None

    # the units of the step in the order it runs them: (block, the
    # block's ops, index, times, what the block hands on besides). A
    # `repeat` block's ops come `times` times in the place of their op,
    # which follows them for what it writes itself (the stacked outputs)
    from ..core.executor import _without_forward_only
    lowered = {id(o) for o in _without_forward_only(
        ops, getattr(ctx, "fetch_names", ()))}
    flat = []
    for i, o in enumerate(ops[:marker]):
        if o.type == "repeat":
            sub = o.attr("sub_block")
            handed = set(o.attr("update_names") + o.attr("output_names"))
            flat += [(sub, list(sub.ops), j, int(o.attr("times")), handed)
                     for j in range(len(sub.ops))]
        flat.append((block, ops, i, 1, set()))
    is_region = lambda unit: unit[1][unit[2]].type == "recompute_block"
    last_at = max((k for k, u in enumerate(flat) if is_region(u)),
                  default=None)
    _LAST.clear()
    for what in ("regions", "rows"):
        _PLAN.set(0, kind=LOSS_BLOCKS, what=what)
    if last_at is None:
        return nothing

    candidates, stream, head, widest, at_loss = [], 0, 0, 0, 0
    largest = 0, 0        # (the term, the largest region's own values)
    first_of, region = {}, 0     # a block's first region, in step order
    fetched, in_blocks, block_rows = set(getattr(ctx, "fetch_names", ())), 0, 0
    try:
        for k, (blk, blk_ops, i, times, handed) in enumerate(flat):
            o = blk_ops[i]
            if o.type != "recompute_block":
                size = sized(blk, o)
                if blk is block and id(o) not in lowered:
                    continue                 # (layers.forward_only)
                if k < last_at:
                    stream += size * times
                else:        # (of a block's visits the last alone)
                    stream += size * (times - 1)
                    head, widest = head + size, max(widest, size)
                continue
            sub = o.attr("sub_block")
            per_visit = _regions_in(blk_ops)
            if id(blk) not in first_of:
                first_of[id(blk)] = region
                region += times * per_visit
            indices = tuple(first_of[id(blk)] + t * per_visit
                            + _regions_in(blk_ops[:i]) for t in range(times))
            own, own_widest, ends_in_loss = 0, 0, False
            mul, _, never_made, rows, columns = _in_row_blocks(
                ctx, sub.ops, _exports(blk, blk_ops, i, fetched | handed),
                lambda name: var_of(sub, name).shape, count) or (
                    None, None, frozenset(), 0, 0)
            for j, m in enumerate(sub.ops):
                size = sized(sub, m) - sum(
                    nbytes[n] for n in set(m.output_names) & never_made)
                own, own_widest = own + size, max(own_widest, size)
                ends_in_loss |= m.type in ("softmax_with_cross_entropy",
                                           "cross_entropy")
                for name, size, seconds, op_id in () if m is mul \
                        else priced(sub, sub.ops, j):
                    # (six digits: equals stay equal whatever their
                    # size; a block's visits keep a value each)
                    candidates.append((
                        float("%.6g" % (seconds / max(size, 1))), name,
                        size * times, k == last_at,
                        size * (times - (k == last_at)), op_id, indices))
            if rows:
                # what the block form holds at its backward's worst: the
                # logits' gradient whole, in the logits' dtype, beside
                # ONE block's logits and, in float32, its softmax and
                # its gradient
                out = mul.output("Out")[0]
                whole = elements[out] * itemsize(sub, out)
                at_loss = max(at_loss, own + whole + rows * columns * (
                    itemsize(sub, out) + 8))
                in_blocks, block_rows = in_blocks + times, rows
            elif ends_in_loss:
                at_loss = max(at_loss, own + own_widest)
            else:
                largest = max(largest, ((
                    _VISITED_REGION_TIMES if times > 1 else 2) * own, own))
            stream += times * sum(nbytes[n] for n in set(o.output("Out")) & (
                _later_reads(blk_ops, i) | handed))
    except _Unsized as e:
        if limit:
            _LOG.info("recompute: the shape of %s does not say its size; "
                      "nothing is kept", e)
        return nothing
    _PLAN.set(in_blocks, kind=LOSS_BLOCKS, what="regions")
    _PLAN.set(block_rows, kind=LOSS_BLOCKS, what="rows")
    if not limit:
        return nothing
    if getattr(ctx.executor, "_keep_nothing", False):
        _LOG.info("recompute: a plan of nothing (the step did not "
                  "compile with the regions' plan); %d regions run their "
                  "head and loss in blocks of %d rows", in_blocks,
                  block_rows)
        return nothing
    loops = [o for o in ops[:marker] if o.type == "repeat"]
    # the float32 gradients of the parameters that several visits read
    # are held from the last visit's backward to the first's
    wrt = set(ops[marker].attr("param_names") or ())
    shared = sum(env[n].size * env[n].dtype.itemsize for n in set().union(
        *(_op_reads(o) for o in loops if int(o.attr("times")) > 1)) & wrt
        if n in env)
    visits = sum(u[3] for u in flat if is_region(u))
    state = sum(env[n].size * env[n].dtype.itemsize
                for n, v in block.vars.items() if v.persistable and n in env)
    # the room at the head, for all that is kept, and at the last
    # region's backward, for what the regions before it keep
    at_head = max(0, limit - state - shared - stream - (head + widest))
    before_last = max(0, limit - state - shared - stream
                      - max(largest[0], at_loss))
    kept_ops, kept_names, all_kept, early_kept = {}, {}, 0, 0
    by_kind = {name: [0, 0, 0] for name in (
        MUL_OUT, CONV_OUT, DELTA_OUT, EXPERTS_OUT, EXPERTS_ROUTE,
        EXPERTS_WEIGHTS)}
    # (a stable sort: the last region's first among equals, then
    # the step's order)
    for _, name, size, is_last, early, op_id, indices in sorted(
            candidates, key=lambda c: (-c[0], not c[3])):
        kind = by_kind[name]
        kind[0] += len(indices)
        if all_kept + size > at_head or early_kept + early > before_last:
            continue
        all_kept, early_kept = all_kept + size, early_kept + early
        kind[1:] = kind[1] + len(indices), kind[2] + size
        if op_id is None:
            for r in indices:
                kept_names.setdefault(r, set()).add(name)
        else:
            kept_ops[op_id] = name
    for name, counts in by_kind.items():
        for what, value in zip(("candidates", "admitted", "admitted_bytes"),
                               counts):
            _PLAN.set(value, kind=name, what=what)
            if name == MUL_OUT:
                _MUL_PLAN.set(value, what=what)
    _MUL_PLAN.set(before_last, what="budget_bytes")
    _PLAN.set(before_last, kind="all", what="budget_bytes")
    _PLAN.set(at_head, kind="all", what="head_budget_bytes")
    _LAST.update(limit=limit, state=state + shared, kept=all_kept,
                 kept_before_last=early_kept, stream=stream,
                 head=head + widest, region=max(largest[0], at_loss))
    _LOG.info(
        "recompute: kept %s; %d bytes in all of a room of %d at the head, "
        "%d of them in the regions before the last of a room of %d at its "
        "backward (the device's limit %d less state %d, stream %d and: "
        "head %d + %d; largest region %d x %d); %d regions run their head "
        "and loss in blocks of %d rows",
        ", ".join("%d of %d %s (%d bytes)" % (c[1], c[0], name, c[2])
                  for name, c in sorted(by_kind.items()) if c[0])
        or "nothing",
        all_kept, at_head, early_kept, before_last, limit, state, stream,
        head, widest, largest[0] // max(largest[1], 1), largest[1],
        in_blocks, block_rows)
    if shared or at_loss:
        _LOG.info(
            "recompute: %d regions a step, the visits of a `repeat` block "
            "counted each; the room is less by %d bytes of float32 "
            "gradients of the parameters that several visits read, and a "
            "region that ends in the loss holds %d bytes at its backward",
            visits, shared, at_loss)
    return kept_ops, {r: frozenset(n) for r, n in kept_names.items()}


# the last plan's reckoning, for the line that sets the compiled step's
# memory_analysis() beside it (compiled_step)
_LAST = {}


def plans(program):
    """Whether `program`'s step is lowered under a regions' plan: its
    main block, or a `repeat` block of it, holds a recompute region
    before a gradient marker. The executor asks before a step's first
    call (Executor._first_compile)."""
    ops = program.global_block().ops
    marker = next((i for i, o in enumerate(ops) if o.type in (
        "backward_marker", "calc_gradient_marker")), None)
    return marker is not None and any(
        o.type == "recompute_block" or o.type == "repeat"
        and _regions_in(o.attr("sub_block").ops) for o in ops[:marker])


def compiled_step(memory, fell_back=False):
    """Say what the compiled step holds beside what the plan reckoned:
    `memory` is the executable's memory_analysis() (None where the
    backend gives none), read by the executor at the step's first call.
    `fell_back`: this is the second lowering, with a plan of nothing."""
    if fell_back:
        _FALLBACKS.inc()
    if memory is None:
        return
    sizes = {what: int(getattr(memory, what + "_size_in_bytes", 0))
             for what in ("argument", "output", "alias", "temp")}
    limit = _LAST.get("limit", 0)
    for what, value in dict(sizes, limit=limit).items():
        _COMPILED.set(value, what=what)
    held = sizes["argument"] + sizes["temp"]
    said = "recompute: the compiled step holds %d bytes: arguments %d + " \
        "temporaries %d (outputs %d, %d of them the arguments' own)" % (
            held, sizes["argument"], sizes["temp"], sizes["output"],
            sizes["alias"])
    if not _LAST:
        _LOG.info("%s; %s", said, "a plan of nothing, after the compile with "
                  "the plan ran out of memory" if fell_back
                  else "no plan was made")
        return
    _LOG.info(
        "%s, %d under the device's limit of %d; the plan reckoned %d: "
        "state %d + stream %d + the larger of [head %d + kept %d] and "
        "[region %d + kept before the last %d]", said, limit - held, limit,
        _LAST["state"] + _LAST["stream"] + max(
            _LAST["head"] + _LAST["kept"],
            _LAST["region"] + _LAST["kept_before_last"]),
        _LAST["state"], _LAST["stream"], _LAST["head"], _LAST["kept"],
        _LAST["region"], _LAST["kept_before_last"])


def _in_row_blocks(ctx, sub_ops, exported, shape_of, elements_of):
    """``(mul, loss, never made, rows, V)`` where a region of these ops
    is lowered in blocks of `rows` rows (_head_and_loss says which
    regions, _block_rows at how many rows), else None: so under
    `is_test` and a mesh (a block of rows would cut across the batch's
    shards), which lower as ever. `shape_of` and `elements_of` say a
    variable's shape and size by its name: the lowering from the values
    it holds, the plan from the Program, and they agree."""
    if ctx.is_test or ctx.mesh is not None:
        return None
    head = _head_and_loss(sub_ops, exported, shape_of)
    if not head:
        return None
    mul, loss, never_made, k, v = head
    rows = _block_rows(elements_of(mul.input("X")[0]) // k, v)
    return (mul, loss, never_made, rows, v) if rows else None


def _loss_in_row_blocks(sctx, sub_ops, mul, loss, never_made, rows):
    """Lower a region that is a head and its loss (_head_and_loss) as
    ONE function in blocks of `rows` rows under a backward rule of its
    own, into sctx.env. The rule IS the region's recompute, so the
    function stands outside jax.checkpoint:

    * forward, a block at a time: the block's logits as the `mul` makes
      them (math.mul_rows: the operands' precision, the accumulator and
      the rounding are its own), then the rows' loss in float32
      (loss.hard_label_rows). Out come the op's `Loss` and, kept for
      the backward, the operands, the labels and the two parts of the
      rows' log-sum-exp: no ``[N, V]`` value outlives a block.
    * backward, a block at a time: the logits made again by the same
      product (the same bits), their gradient under the ROWS' own
      cotangents (loss.hard_label_rows_grad) rounded to the logits'
      dtype; the blocks joined into ONE ``[N, V]`` gradient, which the
      `mul`'s own two transposes read (jax.linear_transpose of
      mul_rows): `dx` and `dW` are the whole products they would be
      under autodiff, in its order of summation.

    The blocks are unrolled, so every device op carries ONE scope: the
    products, forward, made again and transposed, the `mul` row's
    (``mul.<seq>``), the loss's arithmetic and its gradient the
    `softmax_with_cross_entropy` row's; the product made again carries
    inside it the name of every region's second forward
    (_SECOND_FORWARD), so a trace's readers book it as one and not as
    the backward it is called from; both rows are written as ever,
    the `mul`'s with `row_blocks` = (blocks, rows). The ops themselves
    are traced for their shapes alone (what a row says of a value that
    is never made), and with NaN guards on, the guard of each such
    value reads the rows' log-sum-exp, which is non-finite where a
    logit is NaN or +inf."""
    from ..core.executor import _lower_op, _record_nan_guards
    env, guarded = sctx.env, sctx.check_nan
    sctx.check_nan = False
    scopes = {}

    def shapes_alone(ctx2, op2):
        names = [n for n in op2.input_names if n in env]

        def lowered(*values):
            ctx2.env = dict(env, **dict(zip(names, values)))
            registry.lookup(op2.type).lower(ctx2, op2)
            return {n: ctx2.env[n] for n in op2.output_names}

        try:
            env.update(jax.eval_shape(lowered, *(env[n] for n in names)))
        finally:
            ctx2.env = env

    for op2 in sub_ops:
        scopes[id(op2)] = "%s.%d" % (op2.type, sctx._op_seq)
        if not set(op2.output_names) & never_made:
            _lower_op(sctx, op2)
            continue
        _lower_op(sctx, op2, lower=shapes_alone)
        if op2 is mul:
            x2, _ = _flatten2d(env[mul.input("X")[0]],
                               mul.attr("x_num_col_dims", 1))
            sctx.note(row_blocks=(x2.shape[0] // rows, rows))
        elif op2 is loss:
            # (outside the op's scope: inside it every product would
            # carry the loss's scope before its own)
            x_name = mul.input("X")[0]
            env[loss.output("Loss")[0]], lse, handed_on = _rows_function(
                mul, rows, scopes[id(mul)], scopes[id(loss)])(
                    x2, env[mul.input("Y")[0]], env[loss.input("Label")[0]])
            env[x_name] = handed_on.reshape(env[x_name].shape)
    if guarded:
        sctx.check_nan = True
        env.update(dict.fromkeys(never_made, lax.stop_gradient(lse)))
        for op2 in sub_ops:
            _record_nan_guards(sctx, op2)
    for name in never_made:
        del env[name]


def _rows_function(mul, rows, mul_scope, loss_scope):
    """``f(x2 [N, K], w, label) -> (loss [N, 1], the rows' log-sum-exp
    [N, 1], x2)`` of _loss_in_row_blocks, its device ops under the two
    ops' scopes.

    The ORDER is the function's own to state (lax.optimization_barrier:
    no device op, no copy), for the data's order states none and XLA
    then holds every block of every visit at once (the step of
    `ouro_train_T8k` compiled for a described v5e: 3 GB of logits at
    the forward's end, where it had put all four visits' heads, and as
    much at the backward's start). A block waits for the block before
    it. The function hands x2 ON, behind its loss, and what reads the
    head's input after the region reads it from here: so the stack's
    next visit waits for this one's head, and the cotangent of all
    that comes INTO the rule, which waits for it: a visit's head goes
    backward after the visits behind it have, as its layers do. And
    what the rule reads of the forward it reads behind a barrier, as a
    region's second forward does what jax.checkpoint kept: XLA would
    else take the products made again for the forward's and hold every
    block's logits from there to here."""
    product = functools.partial(mul_rows, mul)
    in_mul = functools.partial(jax.named_scope, mul_scope)
    in_loss = functools.partial(jax.named_scope, loss_scope)
    behind = lambda value, done: lax.optimization_barrier((value, done))

    def blocks(*values):
        """(the block's first row, its rows of each value)"""
        return ((at,) + tuple(v[at:at + rows] for v in values)
                for at in range(0, len(values[0]), rows))

    def forward(x2, w, label):
        label, parts = label.reshape(-1, 1), []
        for _, x_b, label_b in blocks(x2, label):
            if parts:
                x_b, parts[-1] = behind(x_b, parts[-1])
            with in_mul():
                logits = product(x_b, w)
            with in_loss():
                parts.append(hard_label_rows(logits, label_b))
        with in_loss():
            made, top, total = (jnp.concatenate(p) for p in zip(*parts))
        handed_on, made = behind(x2, made)
        return (made, top + jnp.log(total), handed_on), (
            x2, w, label, top, total)

    def backward(kept, cotangents):
        x2, w, label, top, total = kept
        (x2, top, total), (g, _, after) = behind((x2, top, total),
                                                 cotangents)
        d_logits = None
        for at, x_b, *rows_b in blocks(x2, label, top, total, g):
            if d_logits is not None:
                x_b, d_logits = behind(x_b, d_logits)
            with in_mul(), jax.named_scope(_SECOND_FORWARD):
                logits = product(x_b, w)
            with in_loss():
                # the blocks' gradients into ONE [N, V] value, in place
                part = hard_label_rows_grad(logits, *rows_b)
                if d_logits is None:
                    d_logits = jnp.zeros((len(x2),) + part.shape[1:],
                                         part.dtype)
                d_logits = lax.dynamic_update_slice(d_logits, part, (at, 0))
        with in_mul():
            dx, = jax.linear_transpose(lambda a: product(a, w), x2)(d_logits)
            dw, = jax.linear_transpose(lambda b: product(x2, b), w)(d_logits)
            return dx + after, dw, None

    f = jax.custom_vjp(lambda *operands: forward(*operands)[0])
    f.defvjp(forward, backward)
    return f


@register("recompute_block")
def _recompute_block(ctx, op):
    """Rematerialization region: lower the sub-block under jax.checkpoint
    so its INTERNAL activations are recomputed during the backward pass
    instead of stored — the TPU realization of the reference era's
    memory-optimization capability (memory_optimization_transpiler.py),
    done by the AD system rather than liveness analysis. Grads flow
    through the region; RNG-consuming ops (dropout) reuse one region key,
    so the recompute replays identical masks.

    What a region keeps and does not recompute (its policy: _saves).
    The output and the log-sum-exp rows of a flash forward kernel, all
    that the flash backward reads of it, at B x T x H*Dv x 2 bytes
    (bf16) a call, so the kernel runs once a layer. And what the
    block's plan admitted (_plan_kept, made for all the block's regions
    where the first is lowered: every kind of value priced in seconds
    to make it again over its bytes, the costliest a byte first, while
    they fit what the device has free at the head and at the last
    region's backward): the results of `mul` ops that a backward rule
    reads, of `gated_short_conv` and `gated_delta_rule` ops, an expert
    layer's output where a
    norm or a merge reads it (so that the layer's loop runs once), the
    router's scores, choices and sorted pairs, and the held experts'
    weights in their compute dtype; each the value the next op reads,
    at the precision the forward made it. A region still runs TWICE:
    the stream's norms, `qk_norm_rope` / `rope`, the gates, the
    hyper-connections' stages, the scans, the keys and values spread
    under grouped heads, a head's product where the region is the head
    and its loss, and whatever the plan passed over. A region
    with no flash kernel in it on a device that states no limit (the
    CPU) keeps nothing and lowers as under a bare jax.checkpoint.

    A region that is a head and its loss and nothing else
    (_head_and_loss: a `mul`, a hard-label `softmax_with_cross_entropy`
    and reshapes, the logits and the softmax read by nothing else) is
    lowered, outside jax.checkpoint, as ONE function in row blocks
    under a backward rule of its own (_loss_in_row_blocks): the logits
    and their float32 softmax are no values any more, the product runs
    twice (forward, and again a block at a time in the rule), and the
    NaN guards of the values that are never made read the rows'
    log-sum-exp. Soft labels, a `Softmax` that is read or fetched,
    logits handed on, a bias, rows with no block that fits, `is_test`
    and a mesh lower as every other region, bit for bit.

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
    # (a region of a `repeat` block is numbered on from the regions
    # before its visit: _repeat)
    region = getattr(ctx, "_region_base", 0) + _regions_in(
        parent_ops[:my_idx])
    out_names = _exports(ctx.block, parent_ops, my_idx,
                         set(getattr(ctx, "fetch_names", ())))
    in_names = [n for n in op.input("X") if n in ctx.env]

    # the block's plan, made where its first region is lowered
    plan = getattr(ctx, "_kept_plan", None)
    if plan is None:
        plan = ctx._kept_plan = _plan_kept(ctx)
    kept, named_inside = plan[0], plan[1].get(region, frozenset())

    base_env = dict(ctx.env)
    region_key = ctx._rng_fn()
    guard_start = getattr(ctx, "_nan_idx", 0)
    op_seq = getattr(ctx, "_op_seq", 0)
    ctx._op_seq = op_seq + len(block.ops)

    def inside(env, rfn):
        """The context the region's ops are lowered in."""
        sctx = LowerContext(env, rfn, is_test=ctx.is_test,
                            executor=ctx.executor, block=block,
                            mesh=ctx.mesh, static_info=ctx.static_info,
                            fetch_names=getattr(ctx, "fetch_names", ()))
        sctx.check_nan = getattr(ctx, "check_nan", False)
        sctx._nan_idx = guard_start   # program-order guard keys continue
        sctx._op_seq = op_seq         # and so do the ops' scope numbers
        # the op ledger's rows of these ops say which region they sit in
        sctx._op_log, sctx._op_region = ctx._op_log, region
        sctx.kept_ops = kept
        return sctx

    def handed_on(env):
        """A region's exports: its outputs + their @LOD lengths
        (sequence ops inside the region may have changed them) + per-op
        NaN guards (the every-op-output contract holds inside regions
        too)."""
        lods = {n + "@LOD": env[n + "@LOD"] for n in out_names
                if env.get(n + "@LOD") is not None}
        guards = {k: v for k, v in env.items()
                  if k.startswith(_NANGUARD) and k not in base_env}
        return tuple(env[n] for n in out_names), lods, guards

    def f(vals, key):
        env = dict(base_env)
        env.update(zip(in_names, vals))
        counter = [0]

        def rfn():
            counter[0] += 1
            return jax.random.fold_in(key, counter[0])

        sctx = inside(env, rfn)
        for op2 in block.ops:
            _lower_op(sctx, op2)
            names = [kept[id(op2)]] if id(op2) in kept else []
            # (the rule names its result itself, and under its kernels
            # the states beside it, inside its custom rule)
            if names and op2.type != "gated_delta_rule":
                out = op2.output("Out")[0]
                env[out] = checkpoint_name(env[out], names[0])
            if op2.type == "routed_experts":
                names += sorted(named_inside)
            if names:
                sctx.note(kept="+".join(names))
        return handed_on(env)

    _REGIONS.inc()
    head = _in_row_blocks(ctx, block.ops, out_names,
                          lambda name: ctx.env[name].shape,
                          lambda name: ctx.env[name].size)
    if head:
        # (no random key is drawn inside: a product, reshapes, a loss)
        env = dict(base_env)
        mul, loss, never_made, rows, _ = head
        _loss_in_row_blocks(inside(env, None), block.ops, mul, loss,
                            never_made, rows)
        outs, lods, guards = handed_on(env)
        # (the head's input as the rule hands it on: _rows_function)
        x_name = mul.input("X")[0]
        ctx.env[x_name] = env[x_name]
    else:
        policy = _saves(_IN_EVERY_REGION + tuple(sorted(named_inside))) \
            if named_inside else _region_policy
        outs, lods, guards = jax.checkpoint(f, policy=policy)(
            tuple(ctx.env[n] for n in in_names), region_key)
    for n, v in zip(out_names, outs):
        ctx.env[n] = v
    ctx.env.update(lods)
    ctx.env.update(guards)
    ctx._nan_idx = guard_start + len(guards)


_VISITS = _REG.counter(
    "ptpu_repeat_visits_total",
    "visits of `repeat` blocks lowered (`times` a lowering of the op, "
    "none a step)")


@register("repeat")
def _repeat(ctx, op):
    """``layers.repeat``: the sub-block `times` times in a row over the
    carried values, every visit under the same parameters (whose
    gradients are then the sums over the visits: the step differentiates
    one function) and under the scope ``visit_<t>``. The visits' ops are
    numbered on, so a scope names ONE op of ONE visit and the op ledger
    has a row for each, with the visit's regions numbered on too; the
    Program holds the block once.

    inputs:  "X" what the block reads of the parent; "Init" the carried
             values' first
    outputs: "Out" each visit's outputs stacked ``[times, ...]``;
             "Final" what the last visit handed on
    attrs:   sub_block, times, carry_names, update_names, output_names

    Recompute regions inside keep what the PARENT block's plan admits
    (_plan_kept counts a region of this block `times` times); what a
    region hands on to the next visit or out of the block is exported
    from it as what a fetch names is."""
    from ..core.executor import _lower_op
    block, times = op.attr("sub_block"), int(op.attr("times"))
    carries, updates = op.attr("carry_names"), op.attr("update_names")
    handed = op.attr("output_names")
    plan = getattr(ctx, "_kept_plan", None)
    if plan is None:
        plan = ctx._kept_plan = _plan_kept(ctx)
    parent_ops = list(ctx.block.ops) if ctx.block is not None else []
    my_idx = next((i for i, o in enumerate(parent_ops) if o is op), 0)
    before = getattr(ctx, "_region_base", 0) + sum(
        _regions_in(o.attr("sub_block").ops) * int(o.attr("times"))
        if o.type == "repeat" else o.type == "recompute_block"
        for o in parent_ops[:my_idx])
    sctx = LowerContext(ctx.env, ctx._rng_fn, is_test=ctx.is_test,
                        executor=ctx.executor, block=block, mesh=ctx.mesh,
                        static_info=ctx.static_info,
                        fetch_names=tuple(getattr(ctx, "fetch_names", ()))
                        + tuple(updates) + tuple(handed))
    sctx.check_nan = getattr(ctx, "check_nan", False)
    sctx._nan_idx = getattr(ctx, "_nan_idx", 0)
    sctx._op_seq = getattr(ctx, "_op_seq", 0)
    sctx._op_log, sctx._kept_plan = ctx._op_log, plan
    carried = tuple(ctx.get(n) for n in op.input("Init"))
    outs = []
    for t in range(times):
        sctx._region_base = before + t * _regions_in(block.ops)
        ctx.env.update(zip(carries, carried))
        with jax.named_scope("visit_%d" % t):
            for op2 in block.ops:
                _lower_op(sctx, op2)
        carried = tuple(ctx.env[n] for n in updates)
        outs.append([ctx.env[n] for n in handed])
    _VISITS.inc(times)
    ctx._op_seq, ctx._nan_idx = sctx._op_seq, sctx._nan_idx
    for name, value in zip(op.output("Final"), carried):
        ctx.env[name] = value
    for name, values in zip(op.output("Out"), zip(*outs)):
        ctx.env[name] = jnp.stack(values)


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
