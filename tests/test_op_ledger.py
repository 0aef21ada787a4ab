"""The op ledger (``paddle_tpu.trace.ops``, ISSUE 51): where a build
lowers a Program op under the device scope ``<type>.<seq>`` it leaves
one row under the same ``seq``, so that a profile's ``mul.12`` is one
lookup from its weight, shape, region and gradients.

The program of these tests: an embedding and a product fed by a feed
alone into a stream ``[4, 8, 16]``, two ``layers.recompute`` regions of
three products each (region 1's first weight frozen), a head tied to the
embedding's table, Adam. The regions' plan is handed a device limit (the
CPU states none), so it keeps the four results a backward rule reads.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import trace
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import _gather_state, _normalize_feeds
from paddle_tpu.ops import control_flow as CF
from paddle_tpu.trace import runtime as RT

_SCOPE = re.compile(r"\b([A-Za-z_]\w*)\.(\d+)\b")
_ROWS = 4 * 8


def _program(prefix, widths=((64, 32), (128, 24))):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        ids = fluid.layers.data("ids", [8, 1], dtype="int64")
        feat = fluid.layers.data("feat", [8, 16])
        table = fluid.layers.embedding(
            ids, size=[32, 16], param_attr=fluid.ParamAttr(name="word_emb"))
        x = fluid.layers.elementwise_add(table, fluid.layers.fc(
            feat, 16, num_flatten_dims=2, bias_attr=False,
            param_attr=fluid.ParamAttr(name="feat_w")))
        for i, (wide, narrow) in enumerate(widths):
            with fluid.layers.recompute():
                h = fluid.layers.fc(
                    x, wide, num_flatten_dims=2, act="tanh", bias_attr=False,
                    param_attr=fluid.ParamAttr(name="layer_%d_up" % i,
                                               trainable=i != 1))
                h = fluid.layers.fc(
                    h, narrow, num_flatten_dims=2, act="tanh",
                    bias_attr=False,
                    param_attr=fluid.ParamAttr(name="layer_%d_mid" % i))
                x = fluid.layers.elementwise_add(x, fluid.layers.fc(
                    h, 16, num_flatten_dims=2, bias_attr=False,
                    param_attr=fluid.ParamAttr(name="layer_%d_down" % i)))
        logits = fluid.layers.tied_head(
            x, main.global_block().var("word_emb"))
        loss = fluid.layers.mean(fluid.layers.square(logits))
        forward = main.clone(for_test=True)
        fluid.optimizer.Adam(1e-3).minimize(loss)
        fluid.Executor(fluid.CPUPlace()).run(startup)
    feeds = {"ids": np.zeros((4, 8, 1), np.int64),
             "feat": np.ones((4, 8, 16), np.float32)}
    return main, forward, scope, feeds, loss


def _run(program, scope, feeds, loss, steps=1, exe=None):
    exe = exe or fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        for _ in range(steps):
            exe.run(program, feed=feeds, fetch_list=[loss])
    return exe


def _built(prefix):
    """The program's step as ``Executor._build`` makes it, not yet
    traced, and the arguments to trace it with."""
    main, _, scope, feeds, loss = _program(prefix)
    state, keys = _gather_state(main, scope)
    feed_arrays, static_info = _normalize_feeds(feeds)
    step = fluid.Executor(fluid.CPUPlace())._build(
        main, tuple(sorted(feed_arrays)), (loss.name,), keys, static_info)
    return step, (state, feed_arrays, jax.random.key(0))


@pytest.fixture
def limit(monkeypatch):
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)


@pytest.fixture(scope="module")
def table():
    """(header, {weight: row} of the products, every row, the step row
    of the build) of the program's train step, built by one run for the
    module's cases."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)
        main, _, scope, feeds, loss = _program("t_")
        _run(main, scope, feeds, loss)
    header, rows = trace.ops(root="exe.step", backward=True)
    fresh = [r for r in trace.steps("exe.step") if r["fresh"]][-1]
    return header, {r["weights"][0]: r for r in rows
                    if r["type"] == "mul"}, rows, fresh


def test_rows_are_the_scopes_of_the_lowered_step(limit):
    """The step's lowered text names ``<type>.<seq>`` for exactly the
    rows of the table, forward, regions and optimizer alike: a scope is
    ONE op of a build, and a row's two halves are its name."""
    step, args = _built("s_")
    text = jax.jit(step).lower(*args).as_text(debug_info=True)
    header, rows = trace.ops()
    assert header["root"] is None and header["step"] is None  # no root open
    assert header["backward"] and header["count"] == len(rows)
    named = {(t, int(n))
             for loc in re.findall(r'loc\("(jit\(step\)[^"]*)"', text)
             for t, n in _SCOPE.findall(loc)}
    assert named == {(r["type"], r["seq"]) for r in rows}
    # the optimizer's scopes number on from the forward's
    last_forward = max(r["seq"] for r in rows if r["type"] == "mean")
    assert {r["type"] for r in rows if r["seq"] > last_forward} == {"adam"}
    assert len({r["seq"] for r in rows}) == len(rows)


@pytest.mark.parametrize("field, want", [
    ("region", {"feat_w": None, "layer_0_up": 0, "layer_0_mid": 0,
                "layer_0_down": 0, "layer_1_up": 1, "layer_1_mid": 1,
                "layer_1_down": 1, "word_emb": None}),
    # the results a backward rule reads; a region's last product goes
    # into the stream and nowhere else
    ("kept", {"feat_w": None, "layer_0_up": "mul_out",
              "layer_0_mid": "mul_out", "layer_0_down": None,
              "layer_1_up": "mul_out", "layer_1_mid": "mul_out",
              "layer_1_down": None, "word_emb": None}),
    ("mkn", {"feat_w": (_ROWS, 16, 16), "layer_0_up": (_ROWS, 16, 64),
             "layer_0_mid": (_ROWS, 64, 32), "layer_0_down": (_ROWS, 32, 16),
             "layer_1_up": (_ROWS, 16, 128), "layer_1_mid": (_ROWS, 128, 24),
             "layer_1_down": (_ROWS, 24, 16),
             # the tied head contracts the table's second dimension
             "word_emb": (_ROWS, 16, 32)}),
    # a product fed by a feed alone has no x, a frozen weight no w
    ("grads", {"feat_w": ("w",), "layer_0_up": ("x", "w"),
               "layer_0_mid": ("x", "w"), "layer_0_down": ("x", "w"),
               "layer_1_up": ("x",), "layer_1_mid": ("x", "w"),
               "layer_1_down": ("x", "w"), "word_emb": ("x", "w")}),
    ("operand_dtype", dict.fromkeys(
        ("feat_w", "layer_0_up", "layer_0_mid", "layer_0_down",
         "layer_1_up", "layer_1_mid", "layer_1_down", "word_emb"),
        "float32"))])
def test_a_products_row_is_what_the_program_says(table, field, want):
    products = table[1]
    assert {w: r[field] for w, r in products.items()} == want


def test_a_row_names_its_values_as_the_trace_held_them(table):
    header, products, rows, fresh = table
    assert (header["root"], header["backward"]) == ("exe.step", True)
    assert header["count"] == len(rows) and header["t_build"] > 0
    # the step whose row of the step ledger is fresh built it
    assert header["step"] == fresh["step"]
    assert fresh["t_enter"] < header["t_build"] < fresh["t_exit"]
    up = products["layer_1_up"]
    (x,), (y,) = up["inputs"]["X"], up["inputs"]["Y"]
    assert x[1:] == ((4, 8, 16), "float32")
    assert y == ("layer_1_up", (16, 128), "float32")
    assert up["outputs"]["Out"][0][1:] == ((4, 8, 128), "float32")
    by_type = {}
    for r in rows:
        by_type.setdefault(r["type"], []).append(r)
    assert by_type["lookup_table"][0]["weights"] == ("word_emb",)
    assert "recompute_block" not in by_type
    # every op inside a region says so, the ops round them do not
    assert sorted({r["region"] for r in by_type["tanh"]}) == [0, 1]
    assert {r["region"] for r in by_type["adam"]} == {None}
    assert len(by_type["adam"]) == 7           # layer_1_up is frozen
    assert all("mkn" not in r and "grads" not in r for r in rows
               if r["type"] != "mul")


def test_amp_shows_in_the_operand_dtype(limit):
    main, _, scope, feeds, loss = _program("a_")
    fluid.amp.enable_amp()
    try:
        _run(main, scope, feeds, loss)
    finally:
        fluid.amp.enable_amp(False)
    _, rows = trace.ops(root="exe.step", backward=True)
    muls = [r for r in rows if r["type"] == "mul"]
    assert {r["operand_dtype"] for r in muls} == {"bfloat16"}
    # the row's inputs are the variables' own, before the cast
    assert {r["inputs"]["Y"][0][2] for r in muls} == {"float32"}


def test_the_for_test_clone_and_the_start_up_program_are_other_tables(limit):
    main, forward, scope, feeds, loss = _program("f_")
    exe = _run(main, scope, feeds, loss)
    _run(forward, scope, feeds, loss, exe=exe)
    header, rows = trace.ops(root="exe.step", backward=False)
    assert not header["backward"]
    muls = [r for r in rows if r["type"] == "mul"]
    assert len(muls) == 8 and all(r["grads"] == () for r in muls)
    assert not any(r["type"] == "adam" for r in rows)
    # nothing is differentiated: the regions' plan keeps nothing
    assert {r["kept"] for r in rows} == {None}
    assert {r["region"] for r in muls} == {None, 0, 1}
    # the train step's table is still there to be asked for
    assert trace.ops(root="exe.step", backward=True)[0]["backward"]
    assert trace.ops(root="pexe.step") is None


def test_steps_after_the_build_add_no_row_and_no_build(limit):
    main, _, scope, feeds, loss = _program("n_")
    exe = _run(main, scope, feeds, loss)
    builds = list(RT._OP_BUILDS)
    before = trace.ops(root="exe.step", backward=True)
    _run(main, scope, feeds, loss, steps=50, exe=exe)
    assert list(RT._OP_BUILDS) == builds        # the same tables, as they were
    assert trace.ops(root="exe.step", backward=True) == before


def test_a_changed_program_is_a_second_table_and_the_ninth_drops_the_first(
        limit):
    RT._OP_BUILDS.clear()
    first = _program("c0_", widths=((40, 32), (128, 24)))
    _run(first[0], first[2], first[3], first[4])
    assert len(RT._OP_BUILDS) == 2              # its start-up program's too
    oldest = RT._OP_BUILDS[0]
    header0, rows0 = trace.ops(backward=True)
    for i in range(1, 4):
        main, _, scope, feeds, loss = _program(
            "c%d_" % i, widths=((40 + 8 * i, 32), (128, 24)))
        _run(main, scope, feeds, loss)
    header, rows = trace.ops(backward=True)
    assert header["t_build"] > header0["t_build"]
    up = lambda rs: next(r["mkn"] for r in rs
                         if r["weights"] == ("layer_0_up",))
    assert (up(rows0), up(rows)) == ((_ROWS, 16, 40), (_ROWS, 16, 64))
    assert len(RT._OP_BUILDS) == 8 and RT._OP_BUILDS[0] is oldest
    main, _, scope, feeds, loss = _program("c4_")     # the ninth build
    assert len(RT._OP_BUILDS) == 8 and RT._OP_BUILDS[0] is not oldest


def test_a_retrace_of_a_build_writes_its_rows_again(limit):
    step, args = _built("r_")
    jax.make_jaxpr(step)(*args)
    builds, once = len(RT._OP_BUILDS), trace.ops()
    jax.make_jaxpr(step)(*args)
    assert len(RT._OP_BUILDS) == builds and trace.ops() == once


def _leaves(value):
    if isinstance(value, dict):
        for k, v in value.items():
            yield from _leaves(k)
            yield from _leaves(v)
    elif isinstance(value, tuple):
        for v in value:
            yield from _leaves(v)
    else:
        yield value


def test_no_row_holds_an_array_or_a_tracer(table):
    header, _, rows, _ = table
    leaves = list(_leaves(header)) + [x for r in rows for x in _leaves(r)]
    assert len(leaves) > 500
    assert {type(x) for x in leaves} <= {int, float, str, bool, type(None)}


def test_the_eager_interpreter_writes_nothing():
    """A program with a host op runs op by op on every run
    (``_run_eager``): it lowers each time, so it leaves no table."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.fc(x, 3)
        i = fluid.layers.fill_constant([1], "int64", 0)
        arr = fluid.layers.array_write(y, i)
        out = fluid.layers.array_read(arr, i)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    builds = list(RT._OP_BUILDS)
    for _ in range(2):
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[out])
    assert list(RT._OP_BUILDS) == builds


def test_the_accumulating_step_writes_the_rows_of_the_plain_one(limit):
    """``_lower_with_grad_accum`` traces its forward twice (a probe and
    the scan's body): one table, under ``pexe.step``, with the plain
    step's rows."""
    from paddle_tpu import parallel
    main, _, scope, feeds, loss = _program("g_")
    _run(main, scope, feeds, loss)
    _, plain = trace.ops(root="exe.step", backward=True)
    with fluid.scope_guard(scope):
        pexe = fluid.ParallelExecutor(
            loss_name=loss.name, main_program=main, scope=scope,
            strategy=parallel.DistributedStrategy(
                gradient_accumulation_steps=2))
        pexe.run([loss], feed={k: np.concatenate([v, v])
                               for k, v in feeds.items()})
    header, rows = trace.ops(root="pexe.step")
    assert header["backward"] and header["count"] == len(plain)
    key = lambda r: (r["seq"], r["type"], r["weights"], r["region"],
                     r.get("grads"))
    assert [key(r) for r in rows] == [key(r) for r in plain]


def test_checkpoints_backward_names_its_second_forward(limit):
    """What ``chipbench/oplog.py`` leans on in jax 0.9.0: of the ops a
    differentiated ``jax.checkpoint`` region lowers to, those of its
    second forward carry ``rematted_computation/`` in their names, those
    of its backward ``transpose(jvp(`` without it, and its first
    forward neither."""
    def region(x, w):
        with jax.named_scope("mul.3"):
            return jnp.tanh(x @ w)

    def loss(w, x):
        with jax.named_scope("mean.5"):
            return jnp.sum(jax.checkpoint(region)(x, w) ** 2)

    text = jax.jit(jax.value_and_grad(loss)).lower(
        jnp.ones((8, 8)), jnp.ones((4, 8))).as_text(debug_info=True)
    names = {n for n in re.findall(r'loc\("(jit\(loss\)[^"]*)"', text)
             if "mul.3" in n}
    second = {n for n in names if "rematted_computation/" in n}
    backward = {n for n in names - second if "transpose(jvp(" in n}
    forward = names - second - backward
    prim = lambda ns: {n.rsplit("/", 1)[1] for n in ns}
    assert prim(forward) == {"dot_general", "tanh"}
    # the second forward makes the product and the tanh again
    assert {"dot_general", "tanh"} <= prim(second)
    assert all(n.split("/")[1].startswith("transpose(jvp(")
               for n in second | backward)
    # the backward's two products, one a gradient
    assert "dot_general" in prim(backward) and "tanh" not in prim(backward)
    assert "transpose" in prim(backward) or "mul" in prim(backward)


# -- a row says which module of the model built its op (ISSUE 55) -------------

def _two_headed(prefix):
    """A stream, one region and a head, then inside
    ``layers.module("second")`` one more region and the SAME head and
    table once more, a second loss added to the cost, and both terms
    summed over the train steps (``layers.step_sum``)."""
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    L = fluid.layers
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        ids = L.data("ids", [8, 1], dtype="int64")
        table = fluid.ParamAttr(name="word_emb")
        fc = lambda x, name: L.fc(x, 16, num_flatten_dims=2, act="tanh",
                                  bias_attr=False,
                                  param_attr=fluid.ParamAttr(name=name))
        x = L.embedding(ids, size=[32, 16], param_attr=table)
        with L.recompute():
            x = L.elementwise_add(x, fc(x, "first_w"))
        head = lambda x: L.mean(L.square(L.tied_head(
            x, main.global_block().var("word_emb"))))
        first = head(x)
        L.step_sum(first, "first_loss_sum")
        with L.module("second"):
            again = L.embedding(ids, size=[32, 16], param_attr=table)
            with L.recompute():
                x = L.elementwise_add(x, fc(again, "second_w"))
            second = head(x)
            L.step_sum(second, "second_loss_sum")
        loss = L.elementwise_add(first, L.scale(second, 0.5))
        forward = main.clone(for_test=True)
        fluid.optimizer.SGD(1.0).minimize(loss)
        fluid.Executor(fluid.CPUPlace()).run(startup)
    return main, startup, forward, scope, {
        "ids": np.arange(32).reshape(4, 8, 1) % 32}, (loss, first, second)


def test_a_row_says_which_module_built_its_op(limit):
    main, startup, forward, scope, feeds, (loss, first, second) = \
        _two_headed("m_")
    # a table shared by name is initialised ONCE
    inits = [n for op in startup.global_block().ops for n in op.output_names]
    assert inits.count("word_emb") == 1
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        table, w1, w2 = (np.array(scope.find_var(n))
                         for n in ("word_emb", "first_w", "second_w"))
        terms = exe.run(main, feed=feeds, fetch_list=[first, second])
        moved = table - np.array(scope.find_var("word_emb"))
    _, rows = trace.ops(root="exe.step", backward=True)
    by_module = {}
    for r in rows:
        by_module.setdefault(r["module"], []).append(r)
    assert set(by_module) == {None, "second"}
    # a region's ops inside the module say so with their region, and
    # the ops round the module say nothing
    assert {(r["type"], r["region"]) for r in by_module["second"]} >= {
        ("lookup_table", None), ("mul", 1), ("tanh", 1), ("mul", None),
        ("step_sum", None)}
    assert {r["region"] for r in by_module[None]} == {None, 0}
    assert {r["type"] for r in by_module[None]} >= {"sgd", "step_sum"}
    weights = lambda rs: sorted(w for r in rs if r["type"] == "mul"
                                for w in r["weights"])
    assert weights(by_module["second"]) == ["second_w", "word_emb"]
    assert weights(by_module[None]) == ["first_w", "word_emb"]
    # the for_test clone's ops carry it too, and its run adds nothing
    # to the sums a train run adds to
    with fluid.scope_guard(scope):
        exe.run(forward, feed=feeds, fetch_list=[loss])
        sums = [float(np.asarray(scope.find_var(n))[0])
                for n in ("first_loss_sum", "second_loss_sum")]
    assert sums == pytest.approx([float(t) for t in terms], rel=1e-6)
    _, rows = trace.ops(root="exe.step", backward=False)
    assert {r["module"] for r in rows} == {None, "second"}

    # one parameter, two uses: at rate 1 the table's step is the sum of
    # its four gradients (two look-ups, two heads)
    def cost(table):
        ids = feeds["ids"][..., 0]
        x = table[ids]
        x = x + jnp.tanh(x @ w1)
        head = lambda x: jnp.mean(jnp.square(x @ table.T))
        return head(x) + 0.5 * head(x + jnp.tanh(table[ids] @ w2))

    np.testing.assert_allclose(moved, jax.grad(cost)(jnp.asarray(table)),
                               rtol=2e-4, atol=1e-6)
