"""What a `layers.recompute` region keeps since ISSUE 52, kind by kind
(ops/control_flow.py _plan_kept): a `mul` result, a short convolution's
result, an expert layer's output where a norm reads it, its router's
results and its weights as it computes with them; the plan at the
benchmark's five region'd cells' declared shapes; and the executor's
first compile, which says what the compiled step holds and lowers once
more with a plan of nothing where the compile runs out of memory.
tests/test_recompute.py holds the region's contract and the helpers
these cases share.
"""

import logging
import os
import sys
import time

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
import test_recompute as TR
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import _gather_state
from paddle_tpu.models import conv_moe, windowed_moe
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import control_flow as CF
from paddle_tpu.ops import flash_attention as FA
from paddle_tpu.ops import short_conv
from paddle_tpu.parallel import moe

_run, _step_jaxpr, _eqns, _equations, _limit_for = (
    TR._run, TR._step_jaxpr, TR._eqns, TR._equations, TR._limit_for)


_KINDS = {"conv": (CF.MUL_OUT, short_conv.CONV_OUT, moe.EXPERTS_ROUTE,
                   moe.EXPERTS_WEIGHTS),
          "windowed": (CF.MUL_OUT, moe.EXPERTS_OUT, moe.EXPERTS_ROUTE,
                       moe.EXPERTS_WEIGHTS)}


def _routed_lm(model, prefix, layers=3):
    """`layers` routed layers of 32 wide, 8 experts with 4 held, top-2, on
    2 x 16 rows, every layer a region, with Adam: "conv" is
    models/conv_moe.py's block (conv, attention, conv mixers; the
    expert layer's output goes into the stream's add and nowhere
    else), "windowed" models/windowed_moe.py's (a norm reads the sum
    of the shared expert's and the routed experts' outputs). Returns
    (program, scope, feeds, fetch names: the loss and every
    parameter's gradient)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    scope = fluid.Scope()
    sizes = dict(vocab_size=64, seq_len=16, n_dense=0, d_model=32, n_head=4,
                 n_kv_head=2, head_dim=8, d_dense=48, d_expert=16,
                 num_experts=8, experts_held=4, top_k=2, embedding_std=1.0,
                 router_std=0.5)
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        if model == "conv":
            cost, _ = conv_moe.conv_moe_lm(
                layer_types=(conv_moe.CONV, conv_moe.FULL, conv_moe.CONV)[:layers],
                conv_width=3, **sizes)
        else:
            cost, _ = windowed_moe.windowed_moe_lm(
                layer_types=(windowed_moe.SLIDING, windowed_moe.FULL,
                             windowed_moe.SLIDING)[:layers], window=8,
                **sizes)
        _, pg = fluid.optimizer.Adam(learning_rate=1e-2).minimize(cost)
        fluid.Executor(fluid.CPUPlace()).run(startup)
    batch = T.make_lm_batch(np.random.RandomState(4), 2, 16, 64)
    feeds = {k: np.asarray(batch[k]) for k in ("src", "label", "mask")}
    return main, scope, feeds, (cost.name,) + tuple(g.name for _, g in pg)


def _one_step(main, scope, feeds, fetch):
    """One train step: the fetched values and, after it, every
    persistable value of the scope (parameters, Adam's moments, the
    routers' loads), by name."""
    got = _run(main, scope, feeds, fetch)
    state, _ = _gather_state(main, scope)
    return got, {n: np.asarray(v) for n, v in state.items()}


def _named(jaxpr, name):
    return sum(e.primitive.name == "name" and e.params["name"] == name
               for e in _eqns(jaxpr))


def _grouped_matmuls(jaxpr):
    """The jaxpr's grouped matmuls of the layer's FORWARD form (rows
    against an expert's weights: lax.ragged_dot), nested jaxprs
    included; the backward's contractions over each expert's own rows
    are ragged_dot_general equations of another form and are counted
    too: only differences are read."""
    return sum(e.primitive.name.startswith("ragged_dot")
               for e in _eqns(jaxpr))


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16_amp"])
@pytest.mark.parametrize("model", sorted(_KINDS))
def test_every_kind_kept_changes_no_bit(monkeypatch, model, amp):
    """A train step with every candidate of every kind kept against
    the regions that keep nothing: the loss, every gradient and every
    updated parameter and moment are the same bits, in float32 and
    under bf16 AMP (a kept value is the value the second forward would
    have made again), the plan's gauges say that each kind the model
    has was admitted, and the counter that its values were saved. Each
    primitive runs by itself here (jax.disable_jit): compiled as one
    program, XLA's CPU backend fuses a value's readers one way where
    the value is kept and another where it is made again, which moves
    a float32 sum's last bit with no kind kept but `mul_out` alone."""
    with fluid.amp.amp_guard(amp), jax.disable_jit():
        none, state = _one_step(*_routed_lm(model, "ek_", 2))
        monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)
        before = {k: CF._KEPT_BYTES.value(name=k) for k in _KINDS[model]}
        kept, kept_state = _one_step(*_routed_lm(model, "ek_", 2))
    for kind in _KINDS[model]:
        counts = [CF._PLAN.value(kind=kind, what=w)
                  for w in ("candidates", "admitted", "admitted_bytes")]
        assert counts[0] == counts[1] > 0 and counts[2] > 0, (kind, counts)
        # (counted at each trace of the regions' gradient)
        saved = CF._KEPT_BYTES.value(name=kind) - before[kind]
        assert saved > 0 and saved % counts[2] == 0, (kind, saved)
    assert all(np.abs(g).sum() > 0 for g in kept[1:])
    for a, b in zip(kept, none):
        np.testing.assert_array_equal(a, b)
    assert sorted(state) == sorted(kept_state)
    for n in state:
        np.testing.assert_array_equal(kept_state[n], state[n], err_msg=n)


def _router_runs(jaxpr):
    """(top-k, sort) equations of the jaxpr: a router's run is one of
    each."""
    names = [e.primitive.name for e in _eqns(jaxpr)]
    return names.count("top_k"), names.count("sort")


def test_a_kept_experts_output_runs_the_layers_loop_once(monkeypatch):
    """Where a norm reads the expert layer's output (through the
    addition of the shared expert's), a region that keeps nothing runs
    the layer's forward loop again before its backward, two grouped
    matmuls a layer; with the output kept by name the gradient's jaxpr
    holds them once. Where the output goes into the stream's addition
    and nowhere else it is no candidate: no value is named for it, and
    keeping every other kind runs no grouped matmul fewer (the loop is
    dead in the second forward already). In both the router's top-k
    and the pairs' sort run twice a layer with nothing kept and once
    with its results kept, and a conv layer's result carries its
    name."""
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: 0)
    nothing = {m: _step_jaxpr(*_routed_lm(m, "el_")) for m in _KINDS}
    assert all(_router_runs(j) == (6, 6) for j in nothing.values())
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)
    windowed = _step_jaxpr(*_routed_lm("windowed", "el_"))
    assert _named(windowed, moe.EXPERTS_OUT) == 3
    assert _grouped_matmuls(windowed) \
        == _grouped_matmuls(nothing["windowed"]) - 2 * 3
    assert _router_runs(windowed) == (3, 3)
    conv = _step_jaxpr(*_routed_lm("conv", "el_"))
    assert _named(conv, moe.EXPERTS_OUT) == 0
    assert CF._PLAN.value(kind=moe.EXPERTS_OUT, what="candidates") == 0
    assert CF._PLAN.value(kind=moe.EXPERTS_ROUTE, what="admitted") == 3
    assert _grouped_matmuls(conv) == _grouped_matmuls(nothing["conv"])
    assert _router_runs(conv) == (3, 3)
    assert _named(conv, short_conv.CONV_OUT) == 2


def _without_names(jaxpr):
    """The jaxpr's equations but the identities that give a value a
    name (the expert layer gives its router's results and its weights
    theirs wherever it is lowered)."""
    return [e for e in _equations(jaxpr) if e[0] != "name"]


@pytest.mark.parametrize("room", ["limit_0", "no_room"])
@pytest.mark.parametrize("model", sorted(_KINDS))
def test_no_room_lowers_every_kind_as_before(monkeypatch, model, room):
    """With a device limit of 0, and with one that leaves no byte at
    the head, the step's jaxpr is, equation for equation but the
    identities that name a value, what the policy of PR 48 gives
    (flash_out, flash_lse, mul_out and nothing else): no value carries
    a name a region gives and nothing is counted as kept."""
    limit = 0
    if room == "no_room":
        monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)
        _step_jaxpr(*_routed_lm(model, "nr_", 2))
        limit = 2 ** 40 - int(CF._PLAN.value(kind="all",
                                             what="head_budget_bytes"))
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: limit)
    kept = CF._KEPT_BYTES.snapshot()
    got = _step_jaxpr(*_routed_lm(model, "nr_", 2))
    assert CF._KEPT_BYTES.snapshot() == kept
    if room == "no_room":
        assert CF._PLAN.value(kind="all", what="head_budget_bytes") == 0
        assert CF._PLAN.value(kind=CF.MUL_OUT, what="candidates") > 0
    for name in (CF.MUL_OUT, short_conv.CONV_OUT, moe.EXPERTS_OUT):
        assert _named(got, name) == 0
    monkeypatch.setattr(CF, "_plan_kept", lambda ctx: ({}, {}))
    monkeypatch.setattr(CF, "_region_policy",
                        jax.checkpoint_policies.save_only_these_names(
                            *FA.KEPT_IN_REGIONS, CF.MUL_OUT))
    assert _without_names(got) == _without_names(
        _step_jaxpr(*_routed_lm(model, "nr_", 2)))


# -- the plan at the benchmark's cells' declared shapes -----------------------
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_V5E_LIMIT = 16909336064       # memory_stats()["bytes_limit"] of a v5e


def built_cell(workload):
    """A benchmark cell's train program as the driver builds it, with
    Adam, built under the caller's AMP guard: (main, startup, the loss's
    name, its feeds as zeros [batch, seq] by name)."""
    sys.path.insert(0, ROOT)
    from chipbench import cells
    cell = cells.load_cell(ROOT, workload)
    cfg, mix = cell["config_file"], cell["traffic_file"]
    seq, batch = int(mix["seq_len"]), int(mix["batch"])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), \
            fluid.scope_guard(fluid.Scope()):
        cost, _ = cells.load_arch(cfg["arch"]).build(cfg, seq)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(cost)
    feeds = {name: np.zeros((batch, seq), var.dtype)
             for name, var in main.global_block().vars.items()
             if var.is_data}
    return main, startup, cost.name, feeds


def abstract_state(exe, startup):
    """The shapes and dtypes of what `startup` leaves in a scope, by
    name: its step traced abstractly, nothing run."""
    return dict(jax.eval_shape(exe._build(startup, (), (), (), {}),
                               {}, {}, jax.random.key(0))[1])


def _cell_plan(monkeypatch, workload):
    """The regions' plan of a benchmark cell's train program under a
    v5e's limit, bf16 AMP on, as ``(ops, names)`` with the gauges set:
    the Program built, nothing run."""
    from paddle_tpu.core.registry import LowerContext
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: _V5E_LIMIT)
    with fluid.amp.amp_guard(True):
        main, startup, _, feeds = built_cell(workload)
        exe = fluid.Executor(fluid.CPUPlace())
        env = dict(abstract_state(exe, startup), **{
            n: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for n, v in feeds.items()})
        return CF._plan_kept(LowerContext(
            env, None, executor=exe, block=main.global_block()))


def _plan_says(kind):
    return tuple(int(CF._PLAN.value(kind=kind, what=w)) for w in (
        "candidates", "admitted", "admitted_bytes"))


def test_the_plan_at_lfm2s_shapes_spends_the_room_the_compiler_confirms(
        monkeypatch):
    """`lfm2_train_T32k` (one sequence of 32,768 rows, five regions):
    the room before the last region is PR 48's budget, 1.62 GB (the
    step compiled for a described v5e with all 14 candidate products
    kept stands 0.67 GiB under the limit, with this plan 1.7: PERF.md
    section 6, PR 52), so not every product fits. Every candidate of
    the LAST region is kept whatever its kind (charged to the head
    alone), the routers' results in all four routed layers, and of the
    products more than the four the parent's plan admitted; what the
    regions before the last keep fits their room."""
    ops, names = _cell_plan(monkeypatch, "lfm2_train_T32k")
    room = int(CF._PLAN.value(kind="all", what="budget_bytes"))
    assert room == 1621578340
    assert int(CF._MUL_PLAN.value(what="budget_bytes")) == room
    mul, conv, route, weights = (_plan_says(k) for k in (
        CF.MUL_OUT, short_conv.CONV_OUT, moe.EXPERTS_ROUTE,
        moe.EXPERTS_WEIGHTS))
    assert mul[0] == 14 and mul[1] > 4
    assert route[:2] == (4, 4)
    assert conv[0] == 4 and conv[1] >= 1 and weights[0] == 4 \
        and weights[1] >= 1
    assert _plan_says(moe.EXPERTS_OUT) == (0, 0, 0)
    # the last region's: both products, the convolution's result, both
    # names of the expert layer
    assert names[4] == {moe.EXPERTS_ROUTE, moe.EXPERTS_WEIGHTS}
    assert sorted(ops.values()).count(short_conv.CONV_OUT) == conv[1]
    in_all = mul[2] + conv[2] + route[2] + weights[2]
    assert in_all <= int(CF._PLAN.value(kind="all",
                                        what="head_budget_bytes"))
    # a conv layer's two products are 537 MB, its convolution's result
    # 134 MB, the layer's weights 176 MB, a router's results 6 MB
    last = 2 * 32768 * (6144 + 2048) + 2 * 32768 * 2048 \
        + 2 * 8 * 3 * 2048 * 1792 + route[2] // 4
    assert 0 < in_all - last <= room


@pytest.mark.parametrize("workload, products, budget, experts_out", [
    ("phi4flash_train_T8k", 38, 3672257708, 0),
    ("trinity_train_T16k", 40, 4148678436, 4),
    ("xing4_train_T4k", 55, 2825879588, 4),
    ("smallthinker_train_T16k", 16, 1800847464, 0)])
def test_the_plan_at_the_other_cells_shapes_admits_no_fewer_than_before(
        monkeypatch, workload, products, budget, experts_out):
    """The four cells PR 48 reckoned: every `mul` candidate is still
    admitted (38 / 40 / 55 / 16), the room is no smaller than PR 48's
    budget, and every candidate of the new kinds fits too; the expert
    layer's output is a candidate where a norm (Trinity) or the
    stream's merge (Xing) reads it and nowhere else."""
    _cell_plan(monkeypatch, workload)
    assert _plan_says(CF.MUL_OUT)[:2] == (products, products)
    assert int(CF._PLAN.value(kind="all", what="budget_bytes")) >= budget
    assert int(CF._PLAN.value(kind="all", what="head_budget_bytes")) \
        >= budget
    assert _plan_says(moe.EXPERTS_OUT)[:2] == (experts_out, experts_out)
    for kind in (moe.EXPERTS_ROUTE, moe.EXPERTS_WEIGHTS):
        said = _plan_says(kind)
        assert said[0] == said[1] == (0 if "phi4" in workload else 4)


# -- the executor's first compile ----------------------------------------------

def compiles(monkeypatch, fail):
    """Count the calls of jax.stages.Lowered.compile and make the first
    `fail` of them raise what a TPU compile that runs out of HBM
    raises."""
    real, calls = jax.stages.Lowered.compile, []

    def compile(self, *args, **kwargs):
        calls.append(self)
        if len(calls) <= fail:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
                "out of memory in memory space hbm. Used 17.10G of 15.75G "
                "hbm.")
        return real(self, *args, **kwargs)

    monkeypatch.setattr(jax.stages.Lowered, "compile", compile)
    return calls


def test_the_first_call_says_what_the_compiled_step_holds(monkeypatch,
                                                          caplog):
    """A program with regions is lowered and compiled ahead of its first
    call, ONCE (the call then finds the executable: the compile log
    holds one trace, one lowering and one compile of `step`), and the
    compiled step's memory_analysis() is said beside the plan's
    reckoning and set in the gauge; later calls compile nothing."""
    from paddle_tpu.monitor import runtime
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)
    calls = compiles(monkeypatch, 0)
    main, scope, feeds, fetch = TR._two_regions("fc_")
    exe = fluid.Executor(fluid.CPUPlace())
    # (by the rows' clock, not by their count: the log is bounded, and
    # a worker that has run other files before this one holds it full,
    # so that every new row pushes an old one out and the count stands)
    since = time.perf_counter()
    of_step = lambda: sorted(
        r["what"] for r in runtime.compile_log()
        if r["end"] >= since and r["fun_name"] in ("step", "jit(step)"))
    once = ["backend_compile_duration", "jaxpr_to_mlir_module_duration",
            "jaxpr_trace_duration"]
    with fluid.scope_guard(scope), caplog.at_level(logging.INFO):
        first = exe.run(main, feed=feeds, fetch_list=list(fetch))
        assert of_step() == once
        exe.run(main, feed=feeds, fetch_list=list(fetch))
    assert len(calls) == 1 and np.isfinite(first[0]).all()
    assert of_step() == once
    said = [r.getMessage() for r in caplog.records
            if "the compiled step holds" in r.getMessage()]
    assert len(said) == 1 and "the plan reckoned" in said[0]
    assert CF._COMPILED.value(what="temp") > 0
    assert CF._COMPILED.value(what="limit") == 2 ** 40
    assert "arguments %d + temporaries %d" % (
        CF._COMPILED.value(what="argument"),
        CF._COMPILED.value(what="temp")) in said[0]


def test_a_step_that_runs_out_of_memory_is_lowered_again_with_a_plan_of_nothing(
        monkeypatch, caplog):
    """Where the compile with the regions' plan fails with
    RESOURCE_EXHAUSTED the executor builds and lowers the step once
    more with a plan of nothing (no value carries `mul_out`, nothing is
    counted as kept), says so and counts it, and the step runs: the
    results are those of the regions that keep nothing. Another error
    is raised as it is."""
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: 0)
    none = _run(*TR._two_regions("oom_"))
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)
    calls = compiles(monkeypatch, 1)
    fell, kept = CF._FALLBACKS.value(), CF._KEPT_BYTES.snapshot()
    with caplog.at_level(logging.INFO):
        got = _run(*TR._two_regions("oom_"))
    assert len(calls) == 2
    assert CF._FALLBACKS.value() - fell == 1
    # (the first lowering's regions were never differentiated... they
    # were: the plan's four results were counted once, in the lowering
    # that did not compile, and none in the second)
    assert CF._KEPT_BYTES.value(name=CF.MUL_OUT) \
        - kept.get((CF.MUL_OUT,), 0) == 4 * 32 * (64 + 32 + 128 + 16)
    said = [r.getMessage() for r in caplog.records]
    assert any("did not compile with the regions' plan" in m for m in said)
    assert any("a plan of nothing" in m for m in said)
    for a, b in zip(got, none):
        np.testing.assert_array_equal(a, b)
    monkeypatch.setattr(
        jax.stages.Lowered, "compile",
        lambda self, *a, **k: (_ for _ in ()).throw(
            RuntimeError("INTERNAL: something else")))
    with pytest.raises(Exception, match="something else"):
        _run(*TR._two_regions("oom_"))
