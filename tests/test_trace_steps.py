"""The step ledger (ISSUE 36): every step root leaves one row on
``time.perf_counter()`` in a bounded ring, with or without a profiler
session or an armed tracer, and ``trace.steps()`` reads it.

What is pinned here, on the CPU (order, attribution and counts, never a
speed): the ring's bound and order; phases inside their root; a sleep
planted in a feed, between two runs, and in a caller that has dropped
its fetch; ``fresh`` on the step that compiled; two threads' rows kept
apart; a collection as an event row; the armed tracer's JSONL row and
``trace stats``; a row's ``step`` equal to its annotation's under the
profiler.
"""

import gc
import glob
import json
import os
import threading
import time

import numpy as np
import jax
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving, trace
from paddle_tpu.models import transformer
from paddle_tpu.models.transformer_infer import TransformerLMInfer
from paddle_tpu.trace import merge
from paddle_tpu.trace import runtime as trt

EXE_PHASES = {"feed", "state", "build", "dispatch", "commit"}
ENGINE_PHASES = {"admit", "prefill", "btab", "dispatch", "fetch", "book"}
ROW_KEYS = {"root", "step", "k", "thread", "t_enter", "t_exit", "outside",
            "device_waited", "fresh", "phases"}


def _trainer():
    """A tiny train program, its feed and a function that runs one
    step: ``step(return_numpy)`` gives the fetched loss."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = fluid.layers.data("x", [8])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(fluid.layers.fc(x, 16, act="relu"), 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    rng = np.random.RandomState(3)
    feed = {"x": rng.rand(4, 8).astype(np.float32),
            "y": rng.rand(4, 1).astype(np.float32)}

    def step(return_numpy=True, feed=feed):
        with fluid.scope_guard(scope):
            return exe.run(main, feed=feed, fetch_list=[loss],
                           return_numpy=return_numpy)[0]
    return step, feed, exe


@pytest.fixture(scope="module")
def lm():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        transformer.transformer_lm(vocab_size=40, max_len=64, n_layer=2,
                                   n_head=2, d_model=32, d_inner=64)
        fluid.Executor(fluid.CPUPlace()).run(startup)
        return TransformerLMInfer(main, scope, 2, 2, 32, 64)


def _mine(since, root="exe.step"):
    """This thread's rows of ``root`` since ``since``."""
    me = threading.get_ident()
    return [r for r in trace.steps(root, since) if r["thread"] == me]


def test_ring_is_bounded_and_in_order():
    t0 = time.perf_counter()
    n = trt._STEP_LOG.maxlen + 500
    for i in range(n):
        with trace.span("exe.step", step=i):
            pass
    rows = trace.steps()
    assert len(rows) == trt._STEP_LOG.maxlen == 4096
    mine = [r for r in rows if r.get("root") == "exe.step"
            and r["t_enter"] >= t0]
    assert [r["step"] for r in mine] == list(range(n - len(mine), n))
    enters = [r["t_enter"] for r in mine]
    assert enters == sorted(enters)
    assert all(r["t_exit"] >= r["t_enter"] for r in mine)
    # what lies between two roots is the later one's ``outside``
    for a, b in zip(mine, mine[1:]):
        assert b["outside"] == pytest.approx(b["t_enter"] - a["t_exit"])


def test_steps_gives_copies_and_filters():
    t0 = time.perf_counter()
    with trace.span("pexe.step", step=3, k=2):
        with trace.phase("pexe.place", step=3):
            pass
    (row,) = _mine(t0, "pexe.step")
    assert set(row) == ROW_KEYS and row["k"] == 2 and row["step"] == 3
    assert set(row["phases"]) == {"place"}
    row["phases"]["place"] = -1.0               # a copy: the ring is whole
    assert _mine(t0, "pexe.step")[0]["phases"]["place"] >= 0.0
    assert _mine(t0, "exe.step") == []
    assert _mine(time.perf_counter(), "pexe.step") == []


def test_a_row_is_in_the_ring_while_its_step_runs():
    t0 = time.perf_counter()
    with trace.span("exe.step", step=11):
        (row,) = _mine(t0)
        assert row["t_exit"] is None and row["step"] == 11
    assert _mine(t0)[0]["t_exit"] is not None


def test_phases_sum_to_no_more_than_their_root():
    step, _, _ = _trainer()
    t0 = time.perf_counter()
    for _ in range(3):
        step()
    rows = _mine(t0)
    assert len(rows) == 3
    for r in rows:
        assert set(r["phases"]) <= EXE_PHASES
        assert {"feed", "state", "commit"} <= set(r["phases"])
        assert sum(r["phases"].values()) <= r["t_exit"] - r["t_enter"]
    # numbered like the executor's steps, one after the other
    assert [r["step"] for r in rows] == list(
        range(rows[0]["step"], rows[0]["step"] + 3))


def test_a_phase_inside_a_phase_and_a_root_inside_a_root():
    t0 = time.perf_counter()
    with trace.span("pexe.step", step=0):
        with trace.phase("pexe.pull", step=0):
            with trace.phase("pexe.commit", step=0):   # counted once
                time.sleep(0.01)
        with trace.span("exe.step", step=5):           # a phase of pexe
            with trace.phase("exe.feed", step=5):
                time.sleep(0.01)
    assert _mine(t0, "exe.step") == []
    (row,) = _mine(t0, "pexe.step")
    assert set(row["phases"]) == {"pull", "exe.step"}
    assert row["phases"]["pull"] >= 0.01
    assert row["phases"]["exe.step"] >= 0.01
    assert sum(row["phases"].values()) <= row["t_exit"] - row["t_enter"]


def test_fresh_on_the_first_run_of_a_program_and_not_the_second():
    step, _, _ = _trainer()
    t0 = time.perf_counter()
    step()
    step()
    first, second = _mine(t0)
    assert first["fresh"] is True and "build" in first["phases"]
    assert "dispatch" not in first["phases"]
    assert second["fresh"] is False and "build" not in second["phases"]
    assert "dispatch" in second["phases"]


def test_a_sleep_in_a_feed_lands_in_feed():
    step, feed, _ = _trainer()

    class SlowBatch:
        """A batch that takes its time to become an array, as one read
        from a slow loader would."""

        def __array__(self, dtype=None, copy=None):
            time.sleep(0.05)
            return feed["x"]
    step()                                           # compiles
    t0 = time.perf_counter()
    step(feed={"x": SlowBatch(), "y": feed["y"]})
    (row,) = _mine(t0)
    assert row["phases"]["feed"] >= 0.05
    others = sum(s for name, s in row["phases"].items() if name != "feed")
    assert others < 0.05


def test_a_sleep_between_two_runs_lands_in_outside_and_the_device_waited():
    step, _, _ = _trainer()
    step(return_numpy=False)
    t0 = time.perf_counter()
    kept = step(return_numpy=False)       # the caller keeps its fetch
    time.sleep(0.05)                      # ... and is late: the host's
    step(return_numpy=False)
    _, late = _mine(t0)
    assert late["outside"] >= 0.05
    assert late["device_waited"] is True
    assert kept.is_ready()
    assert sum(late["phases"].values()) < 0.05


def test_device_waited_true_after_a_numpy_fetch_none_with_no_fetch():
    step, feed, exe = _trainer()
    step()
    t0 = time.perf_counter()
    step()           # the fetch was pulled to the host inside the step:
    step()           # no array is left, and the device had run dry
    assert [r["device_waited"] for r in _mine(t0)] == [True, True]
    empty = fluid.Program()
    exe.run(empty)
    t0 = time.perf_counter()
    exe.run(empty)                       # the step before fetched nothing
    assert _mine(t0)[-1]["device_waited"] is None


def test_a_dropped_fetch_reads_none_and_lives_no_longer(monkeypatch):
    """The device still at work when the step returns, as on a chip:
    said here, not left to the race. On the CPU a step this small is
    done by its root's exit about one time in five under load (61 of
    300 beside six busy workers, PR 44), and a fetch that was done
    reads True whoever holds it."""
    import weakref
    step, _, _ = _trainer()
    step(return_numpy=False)
    t0 = time.perf_counter()
    with monkeypatch.context() as at_work:
        at_work.setattr(trt, "_fetch_done", lambda fetch: False)
        kept = step(return_numpy=False)
    ref = weakref.ref(kept)
    del kept                             # the ledger holds it weakly
    gc.collect()
    assert ref() is None
    step(return_numpy=False)
    assert _mine(t0)[-1]["device_waited"] is None


def test_a_step_that_raises_closes_its_row():
    step, feed, _ = _trainer()
    t0 = time.perf_counter()
    with pytest.raises(Exception):
        step(feed={"x": feed["x"]})      # "y" is missing
    (row,) = _mine(t0)
    assert row["t_exit"] is not None and "error" in row
    step()                               # the thread's next root is a root
    assert len(_mine(t0)) == 2


def test_two_threads_keep_their_rows_apart(lm):
    step, _, _ = _trainer()
    t0 = time.perf_counter()
    stop = threading.Event()

    def train():
        while not stop.is_set():
            step()
    worker = threading.Thread(target=train)
    worker.start()
    try:
        with serving.Engine(lm, slots=2, prefill_chunk=4) as eng:
            eng.generate_many([[1, 5, 9, 7], [1, 8, 6]], [5, 4])
    finally:
        stop.set()
        worker.join(timeout=60)
    assert not worker.is_alive()
    rows = trace.steps(since=t0)
    exe_rows = [r for r in rows if r.get("root") == "exe.step"
                and r["thread"] == worker.ident]
    eng_rows = [r for r in rows if r.get("root") == "engine.step"]
    assert exe_rows and eng_rows
    assert {r["thread"] for r in eng_rows}.isdisjoint({worker.ident})
    assert len({r["thread"] for r in eng_rows}) == 1
    for r in exe_rows:
        assert set(r["phases"]) <= EXE_PHASES
    for r in eng_rows:
        assert set(r["phases"]) <= ENGINE_PHASES
        assert sum(r["phases"].values()) <= r["t_exit"] - r["t_enter"]
    assert any("dispatch" in r["phases"] for r in eng_rows)
    # each thread's ``outside`` is its own caller's, not the other's
    for mine in (exe_rows, eng_rows):
        for a, b in zip(mine, mine[1:]):
            assert b["outside"] == pytest.approx(b["t_enter"] - a["t_exit"])


def test_a_collection_over_the_floor_is_an_event_row(monkeypatch):
    t0 = time.perf_counter()
    gc.collect()
    quick = [r for r in trace.steps(since=t0) if r.get("event") == "gc"
             and r["seconds"] < 1e-3]
    assert quick == []                      # under 1 ms: no row
    monkeypatch.setattr(trt, "_GC_FLOOR_S", 0.0)
    t0 = time.perf_counter()
    with trace.span("exe.step", step=0):
        gc.collect()
    t1 = time.perf_counter()
    events = [r for r in trace.steps(since=t0) if "event" in r]
    assert events and events[-1]["event"] == "gc"
    assert events[-1]["generation"] == 2
    assert t0 <= events[-1]["end"] - events[-1]["seconds"]
    assert events[-1]["end"] <= t1
    assert trace.steps("exe.step", since=t0)[0]["t_enter"] \
        <= events[-1]["end"]
    # ``root=`` keeps step rows alone
    assert all("root" in r for r in trace.steps("exe.step"))


def test_armed_tracer_rows_carry_the_phases(tmp_path):
    step, _, _ = _trainer()
    log = str(tmp_path / "spans.jsonl")
    step()
    trace.enable(log_path=log, sample_rate=1.0, tail_window=0)
    try:
        t0 = time.perf_counter()
        step()
        time.sleep(0.02)
        step()
    finally:
        trace.disable()
    with open(log) as f:
        spans = [json.loads(line) for line in f]
    spans = [s for s in spans if s.get("name") == "exe.step"]
    rows = _mine(t0)
    assert len(spans) == len(rows) == 2
    for span, row in zip(spans, rows):
        attrs = span["attrs"]
        assert attrs["step"] == row["step"]
        assert attrs["phases"] == pytest.approx(row["phases"])
        assert attrs["device_waited"] == row["device_waited"]
        assert attrs["fresh"] is False
    assert spans[1]["attrs"]["outside"] >= 0.02
    # ``trace stats`` shows them with no new verb
    stats = merge.stats_files([log], root_name="exe.step")
    assert stats["rounds"]["count"] == 2
    assert {"exe.feed", "exe.dispatch", "exe.commit"} <= set(
        stats["rounds"]["mean_by_verb_s"])
    assert stats["rounds"]["device_waited"] == {"true": 2}
    text = merge.render_stats(stats)
    assert "exe.dispatch" in text and "device waited" in text


def test_disarmed_and_unprofiled_the_rows_are_there_and_nothing_else(
        tmp_path):
    trace.disable()
    step, _, _ = _trainer()
    t0 = time.perf_counter()
    step()
    assert len(_mine(t0)) == 1 and not trace.enabled()


def test_under_the_profiler_a_row_and_its_annotation_share_step(tmp_path):
    step, _, _ = _trainer()
    step()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        t0 = time.perf_counter()
        step()
        step()
    finally:
        jax.profiler.stop_trace()
    rows = _mine(t0)
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    roots, phases = [], []
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "exe.step":
                        roots.append((ev.start_ns, dict(ev.stats),
                                      ev.duration_ns))
                    elif ev.name.startswith("exe."):
                        phases.append(ev.name)
    roots.sort()
    assert [int(stats["step"]) for _, stats, _ in roots] \
        == [r["step"] for r in rows]
    # the annotations are PR 24's: the phases are still in the trace
    assert {"exe.feed", "exe.state", "exe.dispatch",
            "exe.commit"} <= set(phases)
    # and a row's duration is its annotation's, to the profiler's cost
    for (_, _, dur_ns), row in zip(roots, rows):
        assert dur_ns * 1e-9 == pytest.approx(
            row["t_exit"] - row["t_enter"], abs=5e-3)


def test_run_steps_leaves_one_row_with_k():
    _, feed, _ = _trainer()
    prog, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(prog, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = fluid.layers.data("x", [8])
        y = fluid.layers.data("y", [1])
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(x, 1), y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        t0 = time.perf_counter()
        out = exe.run_steps(prog, feeds=[feed, feed, feed],
                            fetch_list=[loss], scope=scope)
    assert len(out) == 3
    (row,) = _mine(t0)
    assert row["k"] == 3 and row["fresh"] is True
    assert {"feed", "state", "build", "commit"} <= set(row["phases"])


def test_the_rings_cost_is_a_few_clock_reads():
    """Not a speed: a bound loose enough for any machine (the figure is
    PERF.md's, from the chip's host), tight enough to catch a lock, a
    file or a syscall on the path."""
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        with trace.span("exe.step", step=i):
            for name in ("exe.feed", "exe.state", "exe.dispatch",
                         "exe.commit", "exe.build"):
                with trace.phase(name, step=i):
                    pass
    assert (time.perf_counter() - t0) / n < 200e-6
