"""The one skeleton of a compiled step (core/executor.py Executor._step)
under its four entries: ``Executor.run``, ``Executor.run_steps``,
``ParallelExecutor.run`` and ``ParallelExecutor.run_steps``. What the
skeleton owns holds under each: the random key of any ``random_seed``,
the cache key's trace-time toggles, the compile ahead of a region'd
program's first call, and an inner executor that is a whole one.
tests/test_megastep.py, test_parallel.py, test_timeline.py and
test_monitor.py hold the entries' own contracts.
"""

import contextlib
import logging
import time

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, parallel, trace
from paddle_tpu.core import unique_name
from paddle_tpu.ops import control_flow as CF
from paddle_tpu.parallel import mesh as mesh_mod

ENTRIES = ("exe.run", "exe.run_steps", "pexe.run", "pexe.run_steps")
_FEED = {"x": np.random.RandomState(2).rand(4, 16).astype(np.float32)}


def _program(prefix, seed=7, regions=False):
    """x [4, 16] -> fc 32 tanh -> dropout -> fc 16 (a recompute region
    round the two products where asked) -> mean square, SGD. Returns
    (program, its scope with the parameters made, the loss's name)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard(prefix):
        x = fluid.layers.data("x", [16])
        with fluid.layers.recompute() if regions \
                else contextlib.nullcontext():
            h = fluid.layers.fc(x, 32, act="tanh", bias_attr=False)
            h = fluid.layers.dropout(h, 0.25)
            h = fluid.layers.fc(h, 16, bias_attr=False)
        x = fluid.layers.elementwise_add(x, h)
        loss = fluid.layers.mean(fluid.layers.square(x))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        fluid.Executor(fluid.CPUPlace()).run(startup)
    return main, scope, loss.name


@pytest.fixture(scope="module")
def plain():
    return _program("skel_")


@pytest.fixture(autouse=True)
def _the_default_mesh_as_it_was():
    before = mesh_mod.default_mesh()
    yield
    mesh_mod.set_default_mesh(before)


def _caller(entry, main, scope, loss):
    """(the executor, a call of `entry` that runs ONE logical step and
    returns the loss, the entry's step root)."""
    if entry.startswith("pexe"):
        exe = fluid.ParallelExecutor(
            main_program=main, scope=scope,
            mesh=parallel.make_mesh({"dp": 2}, devices=jax.devices()[:2]))
        if entry == "pexe.run":
            call = lambda: exe.run([loss], feed=_FEED)[0]
        else:
            call = lambda: exe.run_steps([loss], feeds=[_FEED])[0][0]
    else:
        exe = fluid.Executor(fluid.CPUPlace())
        if entry == "exe.run":
            call = lambda: exe.run(main, feed=_FEED, fetch_list=[loss],
                                   scope=scope)[0]
        else:
            call = lambda: exe.run_steps(main, feeds=[_FEED],
                                         fetch_list=[loss],
                                         scope=scope)[0][0]
    return exe, call, entry.split(".")[0] + ".step"


@pytest.mark.parametrize("entry", ENTRIES)
def test_a_random_seed_over_4294_runs_a_step(entry):
    """random_seed * 1000003 passes 2**32 from 4295 on: the step's key
    folds it to 32 bits (numpy 2 refuses the conversion otherwise), and
    a seed that ran before gets the key it got."""
    main, scope, loss = _program("seed_%s_" % entry.replace(".", "_"),
                                 seed=5000)
    _, call, _ = _caller(entry, main, scope, loss)
    assert np.isfinite(call()).all()


def test_a_random_seed_over_4294_runs_a_host_op_program():
    """The eager path (a program with host ops: a tensor array's write
    and read) takes its key from the same function."""
    from paddle_tpu.core.executor import _step_keys
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5000
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [16])
        i = fluid.layers.fill_constant([1], "int64", 0)
        arr = fluid.layers.array_write(x, i)
        out = fluid.layers.mean(fluid.layers.dropout(
            fluid.layers.array_read(arr, i), 0.25))
    exe = fluid.Executor(fluid.CPUPlace())
    got, = exe.run(main, feed=_FEED, fetch_list=[out], scope=fluid.Scope())
    assert np.isfinite(got).all() and exe._rng_counter == 1
    # bit for bit what a seed that ran at the parent got, and k of them
    # for a megastep's steps
    main.random_seed = 3
    one = jax.random.key_data(_step_keys(main, 5))
    assert (one == jax.random.key_data(
        jax.random.key(np.uint32(3 * 1000003 + 5)))).all()
    assert (jax.random.key_data(_step_keys(main, 4, 3))[1] == one).all()


def _amp(on):
    fluid.amp.enable_amp(on)


def _check_nan(on):
    flags.set_flag("check_nan_inf", True if on else None)


@pytest.mark.parametrize("toggle", [_amp, _check_nan],
                         ids=["amp", "check_nan_inf"])
@pytest.mark.parametrize("entry", ENTRIES)
def test_a_trace_time_toggle_flipped_builds_afresh(entry, toggle, plain):
    """Both toggles the lowerings read at trace time are in the ONE
    cache key: flipping either gives a fresh build under every entry
    (the step ledger's `fresh`), flipping it back finds the first
    entry again, and an unchanged call builds nothing."""
    main, scope, loss = plain
    exe, call, root = _caller(entry, main, scope, loss)

    def fresh():
        since = time.perf_counter()
        call()
        (row,) = trace.steps(root=root, since=since)
        return row["fresh"]

    try:
        assert fresh() and not fresh()
        toggle(True)
        assert fresh() and not fresh()
        toggle(False)
        assert not fresh()
    finally:
        toggle(False)
    assert len(exe._cache) == 2


@pytest.mark.parametrize("entry", ENTRIES[1:])
def test_a_region_program_is_compiled_ahead_under_every_entry(
        entry, monkeypatch, caplog):
    """As tests/test_recompute_kinds.py shows for ``Executor.run``: a
    program with a recompute region is lowered and compiled ahead of
    its first call, once, and the compiled step's memory_analysis() is
    said beside the plan's reckoning; the next call compiles nothing."""
    monkeypatch.setattr(CF, "_device_limit", lambda ctx: 2 ** 40)
    compiled = []
    real = jax.stages.Lowered.compile

    def compile(self, *args, **kwargs):
        compiled.append(self)
        return real(self, *args, **kwargs)

    monkeypatch.setattr(jax.stages.Lowered, "compile", compile)
    main, scope, loss = _program(
        "ahead_%s_" % entry.replace(".", "_"), regions=True)
    _, call, _ = _caller(entry, main, scope, loss)
    with caplog.at_level(logging.INFO):
        first = call()
        call()
    assert len(compiled) == 1 and np.isfinite(first).all()
    said = [r.getMessage() for r in caplog.records
            if "the compiled step holds" in r.getMessage()]
    assert len(said) == 1 and "the plan reckoned" in said[0]
    assert CF._COMPILED.value(what="limit") == 2 ** 40


def test_a_parallel_executors_inner_executor_is_a_whole_one(plain):
    """ParallelExecutor makes its inner Executor through the
    constructor: every attribute core code reads is there, the mesh is
    handed to the lowerings, and the two share one cache of jitted
    entries."""
    main, scope, loss = plain
    pexe, call, _ = _caller("pexe.run", main, scope, loss)
    whole = vars(fluid.Executor(fluid.CPUPlace()))
    assert set(vars(pexe._exe)) >= set(whole)
    assert pexe._exe._mesh is pexe.mesh
    call()
    (entry,) = pexe._cache.values()
    assert pexe._cache is pexe._exe._cache and callable(entry.lower)
    pexe._exe.close()
    assert not pexe._cache
