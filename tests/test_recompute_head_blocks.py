"""A recompute region that is a head and its loss, lowered in row
blocks under a backward rule of its own (ops/control_flow.py
_loss_in_row_blocks, ISSUE 60): against the plain lowering of the same
region, bit for bit, the loss and both gradients of the product under
cotangents that differ in every row; every region that is anything else
lowered as ever; the head-sized products of a looped model's step
counted; the scopes its device ops carry; the plan's counter; and the
NaN guard of logits that are no value any more."""

import collections
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                  # noqa: E402

import paddle_tpu as fluid                                  # noqa: E402
from paddle_tpu import flags, layers, trace                 # noqa: E402
from paddle_tpu.core import unique_name                     # noqa: E402
from paddle_tpu.core.executor import _normalize_feeds       # noqa: E402
from paddle_tpu.models.looped_lm import looped_lm           # noqa: E402
from paddle_tpu.ops import control_flow as CF               # noqa: E402
from test_recompute import _eqns                            # noqa: E402
from test_recompute_kinds import abstract_state             # noqa: E402

B, T, D, V = 4, 16, 32, 64     # (at these the CPU's float32 dot gives
# a row the same bits whatever rows are beside it)
# a float32 block of 16 of the 64 rows: four blocks
FOUR_BLOCKS = 16 * V * 4


def _said():
    return tuple(int(CF._PLAN.value(kind=CF.LOSS_BLOCKS, what=w))
                 for w in ("regions", "rows"))


def _head_region(case, seq=T):
    """A stream that is a parameter ``[B, seq, D]`` (so that `dx` is a
    gradient to fetch), a head and a hard-label loss in ONE region, and
    a cost that weighs every row by a fed weight of its own: (main,
    scope, feeds, fetch names). `case` bends the region into what the
    block form must leave alone."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    scope = fluid.Scope()
    rng = np.random.RandomState(2)
    weight = rng.rand(B, seq).astype(np.float32) + 0.5
    weight[rng.rand(B, seq) < 0.25] = 0.0               # under the mask
    feeds = {"label": rng.randint(0, V, (B, seq)).astype(np.int64),
             "weight": weight}
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard("hb_"):
        label = layers.data("label", [seq], dtype="int64")
        weigh = layers.data("weight", [seq], dtype="float32")
        x = layers.create_parameter(
            [B, seq, D], "float32", name="hb_stream",
            default_initializer=fluid.initializer.Normal(0., 1.))
        extra = []
        with layers.recompute():
            logits = layers.reshape(layers.fc(
                x, V, num_flatten_dims=2,
                bias_attr=None if case == "bias" else False,
                param_attr=fluid.ParamAttr(name="hb_head")), [-1, V])
            if case == "soft_label":
                feeds["soft"] = rng.dirichlet(np.ones(V), B * seq).astype(
                    np.float32)
                target = layers.data("soft", [V], dtype="float32")
            else:
                target = layers.reshape(label, [-1, 1])
            ell = layers.softmax_with_cross_entropy(
                logits, target, soft_label=case == "soft_label",
                return_softmax=case == "softmax_fetched")
            if case == "softmax_fetched":
                ell, softmax = ell
                extra.append(softmax.name)
            ell = layers.reshape(ell, [-1, seq])
        cost = layers.reduce_sum(layers.elementwise_mul(ell, weigh))
        if case == "logits_read_later":
            cost = layers.elementwise_add(cost, layers.reduce_sum(
                layers.scale(logits, 1e-3)))
        grads = dict((p.name, g.name)
                     for p, g in fluid.backward.append_backward(cost))
        fluid.Executor(fluid.CPUPlace()).run(startup)
    return main, scope, feeds, [cost.name, ell.name, grads["hb_stream"],
                                grads["hb_head"]] + extra


def _run(case, amp, monkeypatch, plain, seq=T, bound=FOUR_BLOCKS):
    """One step's fetches and the op ledger's rows, in row blocks where
    the lowering finds its pattern, or (`plain`) as ever."""
    monkeypatch.setattr(CF, "_LOSS_BLOCK_BYTES", bound)
    if plain:
        monkeypatch.setattr(CF, "_in_row_blocks", lambda *a, **k: None)
    with fluid.amp.amp_guard(amp), jax.disable_jit():
        main, scope, feeds, fetch = _head_region(case, seq)
        out = fluid.Executor(fluid.CPUPlace()).run(
            main, feed=feeds, fetch_list=fetch, scope=scope)
        _, rows = trace.ops(root=None, backward=True)
    monkeypatch.undo()
    return out, rows, _said()


def _floats(rows):
    """The rows without the dtypes of their integer values (a label fed
    as numpy's int64 is JAX's int32 inside jax.checkpoint)."""
    plain = lambda slots: {slot: tuple(
        (n, shape, dtype and dtype.replace("int64", "int32"))
        for n, shape, dtype in values) for slot, values in slots.items()}
    return [dict(r, inputs=plain(r["inputs"]), outputs=plain(r["outputs"]))
            for r in rows]


# -- the block form against the plain lowering --------------------------------

@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16_amp"])
@pytest.mark.parametrize("bound", [FOUR_BLOCKS, 2 ** 28],
                         ids=["four_blocks", "one_block"])
def test_row_blocks_give_the_plain_lowerings_bits(monkeypatch, amp, bound):
    """The cost, every row's loss, `dx` and `dW` of the region in four
    blocks of 16 rows, and in one of all 64, are the bits of the three
    ops under jax.checkpoint: the cotangent differs in every row and is
    zero under a mask. Each primitive runs by itself
    (jax.disable_jit)."""
    blocks, rows, said = _run(None, amp, monkeypatch, plain=False,
                              bound=bound)
    plain, plain_rows, plain_said = _run(None, amp, monkeypatch, plain=True,
                                         bound=bound)
    per_block = 16 if bound == FOUR_BLOCKS else B * T
    assert said == (1, per_block) and plain_said == (0, 0)
    assert all(np.abs(v).sum() > 0 for v in blocks)
    for a, b in zip(blocks, plain):
        np.testing.assert_array_equal(a, b)
    # the ledger's rows are the plain lowering's, but for the word on
    # the product's row: a value that is never made is described as it
    # would have been
    mul, = [r for r in rows if r["type"] == "mul"]
    assert mul.pop("row_blocks") == (B * T // per_block, per_block)
    assert mul["mkn"] == (B * T, D, V) and mul["grads"] == ("x", "w")
    assert mul["operand_dtype"] == ("bfloat16" if amp else "float32")
    assert _floats(rows) == _floats(plain_rows)


AS_EVER = ["rows_not_divisible", "soft_label", "softmax_fetched",
           "logits_read_later", "bias"]


@pytest.mark.parametrize("case", AS_EVER)
def test_anything_else_lowers_as_ever(monkeypatch, case):
    """Rows with no divisor of whole sublane tiles under the bound (4 x
    15: 1, 2, 3, 4, 5, 6 ... 60), soft labels, a `Softmax` that is
    fetched, logits that an op after the region reads, a bias: the
    lowering does not find its pattern, says 0 regions, and every
    fetch is the plain lowering's to the bit."""
    seq = 15 if case == "rows_not_divisible" else T
    name = None if case == "rows_not_divisible" else case
    found, rows, said = _run(name, True, monkeypatch, plain=False, seq=seq)
    plain, plain_rows, _ = _run(name, True, monkeypatch, plain=True, seq=seq)
    assert said == (0, 0) and _floats(rows) == _floats(plain_rows)
    assert not any("row_blocks" in r for r in rows)
    for a, b in zip(found, plain):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("bent", ["is_test", "mesh"])
def test_a_test_run_and_a_mesh_lower_as_ever(monkeypatch, bent):
    """What the lowering reads of its context: a run that is a test, or
    one across a mesh (a block of rows would cut across the batch's
    shards), takes no row blocks where the plain one does."""
    from paddle_tpu.core.registry import LowerContext
    monkeypatch.setattr(CF, "_LOSS_BLOCK_BYTES", FOUR_BLOCKS)
    main, _, _, _ = _head_region(None)
    region, = [o for o in main.global_block().ops
               if o.type == "recompute_block"]
    sub = region.attr("sub_block")
    asked = lambda ctx: CF._in_row_blocks(
        ctx, sub.ops, [], lambda name: sub._find_var_recursive(name).shape,
        lambda name: B * T * D)
    assert asked(LowerContext({}, None))[3:] == (16, V)
    assert asked(LowerContext({}, None, **{bent: True})) is None


# -- a looped model's step ---------------------------------------------------

VOCAB, SEQ, WIDTH, VISITS = 72, 16, 32, 3


def _looped_step(monkeypatch, bound=8 * VOCAB * 4):
    """The train step of a small looped model (2 x 16 rows, blocks of 8)
    as the executor builds it, under bf16 AMP: (the step, its
    arguments' shapes)."""
    monkeypatch.setattr(CF, "_LOSS_BLOCK_BYTES", bound)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), \
            fluid.scope_guard(fluid.Scope()), unique_name.guard("hl_"):
        cost, _ = looped_lm(VOCAB, SEQ, 2, WIDTH, 2, 2, 16, 48, VISITS,
                            name="hl")
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(cost)
    exe = fluid.Executor(fluid.CPUPlace())
    state = abstract_state(exe, startup)
    feeds, static_info = _normalize_feeds({
        "src": np.zeros((2, SEQ), np.int64),
        "label": np.zeros((2, SEQ), np.int64),
        "mask": np.ones((2, SEQ), np.float32)})
    step = exe._build(main, tuple(sorted(feeds)), (cost.name,),
                      tuple(sorted(state)), static_info)
    return step, (state, feeds, jax.random.key(0))


def test_a_visits_head_is_four_products(monkeypatch):
    """The step's jaxpr holds, a visit, the work of FOUR products of
    the head's size (forward, made again, `dx`, `dW`: 4 x 2 N d V
    FLOPs), not the five of a rule that is run again under
    jax.checkpoint; the forward's and the second's come a block at a
    time, the two transposes whole, and the second's carry the name of
    a region's second forward. And the plan's counter says a region a
    visit."""
    with fluid.amp.amp_guard(True):
        step, args = _looped_step(monkeypatch)
        jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    assert _said() == (VISITS, 8)
    rows, flops, names = 2 * SEQ, [], []
    for e in _eqns(jaxpr):
        shapes = [v.aval.shape for v in list(e.invars) + list(e.outvars)]
        if e.primitive.name == "dot_general" and any(
                VOCAB in s for s in shapes):
            names.append(str(e.source_info.name_stack))
            (contract, _), _ = e.params["dimension_numbers"]
            flops.append(2 * e.outvars[0].aval.size * int(np.prod([
                e.invars[0].aval.shape[a] for a in contract])))
    assert sum(flops) == 4 * VISITS * 2 * rows * WIDTH * VOCAB
    in_blocks = [f for f in flops if f == 2 * 8 * WIDTH * VOCAB]
    assert len(in_blocks) == 2 * VISITS * rows // 8
    assert len(flops) - len(in_blocks) == 2 * VISITS
    # by the names a trace's readers tell the passes by: a visit's
    # blocks once bare and once more as a second forward, inside the
    # `mul` row's scope, and the two transposes
    assert sorted(collections.Counter(
        re.sub(r"visit_\d+|mul\.\d+", "_", n) for n in names).items()) == [
            ("jvp(_)/_", VISITS * rows // 8),
            ("transpose(jvp(_))/_/rematted_computation", VISITS * rows // 8),
            ("transpose(jvp(_))/_/transpose(transpose(jvp(_)))/_",
             2 * VISITS)]


def test_the_order_the_rule_states_is_in_the_steps_jaxpr(monkeypatch):
    """The rule's memory rests on an ORDER that only its barriers state
    (_rows_function), and a compile for the chip is what it costs to
    see one lost; the step's jaxpr shows them at no cost. Every block's
    product, forward and made again, reads rows that come out of a
    barrier of its own, but a visit's first forward block; and what
    reads the head's input AFTER the region (the exit gate, the next
    visit's stack) reads the rule's third output, nothing of the
    forward but the head reading the value that went in: else the later
    visits' cotangent would not come into the rule, and a visit's head
    would go backward before the visits behind it."""
    with fluid.amp.amp_guard(True):
        step, args = _looped_step(monkeypatch)
        jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    made = {v: e for e in jaxpr.eqns for v in e.outvars}
    readers = collections.defaultdict(list)
    for e in jaxpr.eqns:
        for v in e.invars:
            if type(v).__name__ != "Literal":
                readers[v].append(e)
    is_a = lambda e, *kinds: e is not None and e.primitive.name in kinds
    forward = lambda eqns: [e for e in eqns if "transpose" not in str(
        e.source_info.name_stack)]

    def barrier_of(v):
        """The barrier that v comes out of, through casts and slices."""
        while is_a(made.get(v), "convert_element_type", "slice"):
            v = made[v].invars[0]
        return made[v] if is_a(made.get(v), "optimization_barrier") else None

    rows = 2 * SEQ
    products = [e for e in jaxpr.eqns if is_a(e, "dot_general")
                and e.outvars[0].aval.shape == (8, VOCAB)]
    assert len(products) == 2 * VISITS * rows // 8
    first = [e for e in products if barrier_of(e.invars[0]) is None]
    assert len(first) == VISITS and first == forward(first)
    # (a barrier of its own each: a block waits for the block before it)
    assert len({id(barrier_of(e.invars[0])) for e in products}
               - {id(None)}) == len(products) - VISITS
    handed_on = [e for e in jaxpr.eqns if is_a(e, "optimization_barrier")
                 and [v.aval.shape for v in e.invars] == [(rows, WIDTH),
                                                          (rows, 1)]]
    assert len(handed_on) == VISITS == len(forward(handed_on))
    for e in handed_on:
        flattened = made[e.invars[0]]
        assert is_a(flattened, "reshape")
        assert forward(readers[flattened.invars[0]]) == [flattened]
        (unflattened,) = readers[e.outvars[0]]
        assert is_a(unflattened, "reshape") and unflattened.outvars[0].aval \
            == flattened.invars[0].aval
        assert forward(readers[unflattened.outvars[0]])


def test_the_products_carry_the_mul_rows_scope(monkeypatch):
    """Every device op of the compiled step that multiplies by the
    head, forward, made again or transposed, carries the scope of the
    `mul` row of ITS visit, and none the loss's: the op ledger's
    readers join a device op to a row by its scope, and a product under
    the loss's scope would be FLOPs without their time. A trace's
    readers tell the three passes by name, and a visit's head is four
    products' work under them: the forward's blocks bare, the blocks
    made again under `rematted_computation` INSIDE the row's scope (the
    rule that orders them is a backward, the work a second forward, and
    it is booked as one), the two whole transposes as the backward.
    The loss's arithmetic is under the `softmax_with_cross_entropy`
    row's."""
    with fluid.amp.amp_guard(True):
        step, args = _looped_step(monkeypatch)
        text = jax.jit(step).lower(*args).compile().as_text()
        _, rows = trace.ops(root=None, backward=True)
    heads = {"mul.%d" % r["seq"] for r in rows if r["type"] == "mul"
             and r["module"] == "loop_head" and r["region"] is not None}
    losses = {"softmax_with_cross_entropy.%d" % r["seq"] for r in rows
              if r["type"] == "softmax_with_cross_entropy"}
    assert len(heads) == len(losses) == VISITS
    named = re.findall(r"(?m)^.*[\[,]%d[\],].* dot\(.*op_name=\"([^\"]*)\""
                       % VOCAB, text)
    by_scope = {}
    for name in named:
        scope = re.search(r"/((?:mul|softmax_with_cross_entropy)\.\d+)/",
                          name)
        assert scope, name
        by_scope.setdefault(scope.group(1), []).append(name)
    assert heads <= set(by_scope) and not losses & set(by_scope)
    for head in heads:
        assert {"second" if "/%s/rematted_computation/" % head in n else
                "bwd" if "transpose(jvp(" in n else "fwd"
                for n in by_scope[head]} == {"fwd", "second", "bwd"}
    assert any(re.search(r"/(%s)/" % "|".join(map(re.escape, losses)), n)
               for n in re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("model", ["latent_moe", "windowed_moe"])
def test_a_head_outside_every_region_counts_no_blocks(monkeypatch, model):
    """The other cells build their head and `lm_cost` outside every
    region: their plans say 0 regions in row blocks."""
    from paddle_tpu.core.registry import LowerContext
    from paddle_tpu.models import latent_moe, windowed_moe
    monkeypatch.setattr(CF, "_LOSS_BLOCK_BYTES", 8 * 64 * 4)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), \
            fluid.scope_guard(fluid.Scope()), fluid.amp.amp_guard(True):
        if model == "latent_moe":
            cost, _ = latent_moe.latent_moe_lm(
                vocab_size=64, seq_len=16, n_layer=2, n_dense=1, d_model=32,
                n_head=2, q_rank=24, kv_rank=16, d_nope=16, d_rope=8,
                d_v=16, d_dense=40, d_expert=24, num_experts=8,
                experts_held=4, top_k=2)
        else:
            cost, _ = windowed_moe.windowed_moe_lm(
                vocab_size=64, seq_len=16, layer_types=(
                    windowed_moe.SLIDING, windowed_moe.FULL), n_dense=1,
                d_model=32, n_head=4, n_kv_head=2, head_dim=8, window=8,
                d_dense=48, d_expert=16, num_experts=8, experts_held=4,
                top_k=2)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(cost)
        exe = fluid.Executor(fluid.CPUPlace())
        env = dict(abstract_state(exe, startup), **{
            n: jax.ShapeDtypeStruct((2, 16), v.dtype)
            for n, v in main.global_block().vars.items() if v.is_data})
        CF._PLAN.set(7, kind=CF.LOSS_BLOCKS, what="regions")
        CF._plan_kept(LowerContext(env, None, executor=exe,
                                   block=main.global_block()))
    assert any(o.type == "recompute_block" for o in main.global_block().ops)
    assert _said() == (0, 0)


# -- the NaN guard -------------------------------------------------------------

def test_a_nan_in_the_head_is_laid_at_the_products_door(monkeypatch):
    """`check_nan_inf` scans every op's outputs; the logits of a region
    in row blocks are no value, so their guard reads the rows'
    log-sum-exp: a NaN planted in the head's weight is reported at the
    `mul`, the first op it spoils, as under the plain lowering."""
    monkeypatch.setattr(CF, "_LOSS_BLOCK_BYTES", FOUR_BLOCKS)
    main, scope, feeds, fetch = _head_region(None)
    exe = fluid.Executor(fluid.CPUPlace())
    flags.set_flag("check_nan_inf", True)
    try:
        exe.run(main, feed=feeds, fetch_list=fetch[:1], scope=scope)
        assert _said() == (1, 16)
        head = scope.get_numpy("hb_head").copy()
        head[3, 7] = np.nan
        scope.set("hb_head", head)
        with pytest.raises(FloatingPointError, match=r"fc_0.tmp_0' of op 'mul'"):
            exe.run(main, feed=feeds, fetch_list=fetch[:1], scope=scope)
    finally:
        flags.set_flag("check_nan_inf", None)
