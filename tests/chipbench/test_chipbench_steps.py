"""The three readers of the program's step ledger
(``step_interval_ms.train``, ``step_stall_pct.train``,
``exe_step_ms.train``; ``chipbench/steps.py``): on windows made by
hand, where the stall, the traced stretch and the events are planted;
None, with the reason said, where the ring is absent or its rows are
not the window's; and through ``run.py`` in a traced CPU rehearsal of
``opt350m_train``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells, steps

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAMES = ("step_interval_ms.train", "step_stall_pct.train",
         "exe_step_ms.train")
PACE, HOST = 0.300, 0.004        # a step's interval; the executor's part


def _rows(n, late=None, first_step=40):
    """``n`` rows of a steady window: a step every ``PACE`` s, ``HOST``
    of it inside the executor. ``late`` maps a row's index to the
    seconds its entry (and every later one) comes late by, and the piece
    to blame: ``"outside"`` or a phase of the step before."""
    rows, t, before = [], 1000.0, None
    for i in range(n):
        phases = {"feed": 0.0005, "state": 0.0005, "dispatch": 0.002,
                  "commit": 0.0005}
        extra, blame = (late or {}).get(i + 1, (0.0, None))
        if blame not in (None, "outside"):
            phases[blame] += extra
        inside = HOST + (extra if blame not in (None, "outside") else 0.0)
        rows.append({
            "root": "exe.step", "step": first_step + i, "k": None,
            "thread": 1, "t_enter": t, "t_exit": t + inside,
            "outside": None if before is None else t - before,
            "device_waited": False, "fresh": False, "phases": phases})
        before = t + inside
        t += PACE + extra
    return rows


def _run(rows, traced=(), window_s=None, steps_=None):
    n = len(rows) if steps_ is None else steps_
    if window_s is None:         # the last block_until_ready drains two
        window_s = rows[-1]["t_exit"] - rows[0]["t_enter"] + 2 * PACE
    host = [{"name": "exe.step", "start": 0.0, "dur": 0.0, "thread": "t",
             "args": {"step": str(s)}} for s in traced]
    return {"train": {"steps": n, "window_s": window_s,
                      "tokens_per_step": 8192,
                      "traced_steps": len(traced), "traced_s": 0.0},
            "trace": {"busy_s": 1.0} if traced else None,
            "spans": {"host": host, "compiles": None} if traced else None}


@pytest.fixture
def ledger(monkeypatch):
    """Plants the rows (and events) ``steps.ledger()`` will give."""
    def plant(rows, events=(), compiles=None):
        everything = sorted(list(rows) + list(events), key=lambda r: r.get(
            "t_enter", r.get("end")))
        monkeypatch.setattr(steps, "ledger", lambda: (
            lambda root=None, since=None: [
                dict(r) for r in everything
                if root is None or r.get("root") == root]))
        monkeypatch.setattr(steps.spans, "compile_log", lambda: compiles)
    return plant


def _read(name, run):
    return cells.load_metric(name).read(run)


def test_a_steady_window_reads_its_pace_no_stall_and_the_host_time(
        ledger, capsys):
    rows = _rows(170)
    ledger(rows)
    run = _run(rows)
    assert _read("step_interval_ms.train", run) == pytest.approx(1e3 * PACE)
    assert _read("step_stall_pct.train", run) == 0.0
    assert _read("exe_step_ms.train", run) == pytest.approx(1e3 * HOST)
    out = capsys.readouterr().out
    # tokens_per_step over the interval, beside the window's own rate
    assert "%.1f tokens/s at that pace" % (8192 / PACE) in out
    assert "dispatch 2.000" in out and "self 0.500" in out
    assert "0 of 169 intervals over 1.5 x the median" in out


def test_one_gap_is_found_with_its_phase_its_events_and_who_was_late(
        ledger, capsys):
    rows = _rows(170, late={100: (13.0, "outside"), 30: (0.5, "feed")})
    rows[100]["device_waited"] = True          # the host was late
    t0, t1 = rows[99]["t_enter"], rows[100]["t_enter"]
    events = [{"event": "gc", "generation": 2, "seconds": 0.8,
               "end": t0 + 1.0, "thread": 1},
              {"event": "gc", "generation": 0, "seconds": 0.002,
               "end": t1 + 0.1, "thread": 1}]           # the next one's
    compiles = [{"what": "backend_compile_duration", "fun_name": "step",
                 "end": t0 + 12.0, "seconds": 11.0},
                {"what": "backend_compile_duration", "fun_name": "early",
                 "end": rows[0]["t_enter"] - 5.0, "seconds": 1.0}]
    ledger(rows, events, compiles)
    run = _run(rows)
    window = steps.of(run)
    assert window["median_s"] == pytest.approx(PACE)
    got = _read("step_stall_pct.train", run)
    assert got == pytest.approx(100 * 13.5 / (169 * PACE + 13.5))
    first, second = steps.stalls(window)
    assert first["step"] == rows[99]["step"] and first["held"] == "outside"
    assert first["excess"] == pytest.approx(13.0)
    assert first["device_waited"] is True
    assert first["after"] == pytest.approx([PACE - HOST] * 2)
    assert [e["what"] for e in first["events"]] == [
        "gc gen 2", "backend_compile_duration step"]
    assert second["step"] == rows[29]["step"] and second["held"] == "feed"
    assert second["events"] == [] and second["device_waited"] is False
    out = capsys.readouterr().out
    assert "2 of 169 intervals over 1.5 x the median" in out
    assert "step %d: 13.300 s, held by outside" % rows[99]["step"] in out
    assert "device_waited at the next entry True, outside before the " \
        "two entries after it 0.2960 0.2960 s" in out
    assert "gc gen 2 0.800 s; backend_compile_duration step 11.000 s" in out
    assert "ended inside: nothing recorded" in out
    # the median is the pace still: the rate with no stall
    assert _read("step_interval_ms.train", run) == pytest.approx(1e3 * PACE)


def test_the_traced_stretch_is_left_out_and_counted(ledger, capsys):
    # the profiler's start before step 3 and its stop after step 8, as
    # the driver makes them, and the traced steps slower on the host
    late = {3: (2.0, "outside"), 9: (1.5, "outside")}
    rows = _rows(170, late=late)
    traced = [r["step"] for r in rows[3:9]]
    for r in rows[3:9]:
        r["t_exit"] += 0.05
    ledger(rows)
    run = _run(rows, traced=traced)
    assert _read("step_stall_pct.train", run) == 0.0
    assert steps.of(run)["left_out"] == len(traced) + 1
    assert _read("step_interval_ms.train", run) == pytest.approx(1e3 * PACE)
    assert _read("exe_step_ms.train", run) == pytest.approx(1e3 * HOST)
    out = capsys.readouterr().out
    assert "7 that touch the traced stretch left out" in out
    assert "164 steps (6 traced left out)" in out


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("why", ["no ring", "rows of another window",
                                 "too few rows", "an open row",
                                 "no annotation for the traced steps"])
def test_reader_returns_none_and_says_why(ledger, monkeypatch, capsys,
                                          name, why):
    rows = _rows(60)
    ledger(rows)
    run = _run(rows)
    if why == "no ring":
        monkeypatch.setattr(steps, "ledger", lambda: None)
        said = "the program keeps none"
    elif why == "rows of another window":
        run = _run(rows, window_s=40.0)      # 60 steps span 18 s
        said = "they are not the window's"
    elif why == "too few rows":
        run = _run(rows, steps_=61)
        said = "60 closed rows of exe.step for a window of 61 steps"
    elif why == "an open row":
        rows[-1]["t_exit"] = None
        ledger(rows)
        said = "closed rows"
    else:
        run = _run(rows, traced=[rows[4]["step"]])
        run["spans"] = {"host": [], "compiles": None}
        said = "holds no exe.step annotation"
    assert _read(name, run) is None
    assert said in capsys.readouterr().out


def test_the_real_ledger_absent_is_none(monkeypatch):
    """A tree with no ring: the guard is ``getattr``, as
    ``spans.compile_log``'s is."""
    from paddle_tpu import trace
    assert steps.ledger() is trace.steps
    monkeypatch.delattr(trace, "steps")
    assert steps.ledger() is None
    assert steps.of(_run(_rows(10))) is None


def test_traced_rehearsal_reports_the_three(tmp_path):
    """``run.py --rehearse --trace 1`` on ``opt350m_train``, with what a
    CPU cannot give taken out: the cell's other per-layer readers want
    the chip's peaks and ``tracing.reduce_rows`` a device plane (both
    the benchmark's), so the cell is cut to the three new metrics in a
    copy and the reduction is stubbed. Everything else is run.py's
    path: the driver's window with its traced stretch, ``cells.
    read_metrics``, the line."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [m for m in bench["per_layer"] if m["name"] in NAMES]
    assert [m["name"] for m in entries] == list(NAMES)
    for m in entries:
        assert m["workloads"] == [w["name"] for w in bench["workloads"]]
        assert (m["source"], m["layer"], m["moves"], m["better"]) == (
            "program_span", "train executor", "tokens_per_s", "lower")
    bench["per_layer"] = entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import runpy, sys\n"
        "sys.path.insert(0, %r)\n"
        "from chipbench import tracing\n"
        "tracing.reduce_rows = lambda rows, chips=1: {\n"
        "    'window_s': 1.0, 'busy_s': 0.5, 'modules': {}, 'ops': {},\n"
        "    'device_ops': [], 'idle_gaps': []}\n"
        "sys.argv = ['chipbench/run.py', '--workload', 'opt350m_train',\n"
        "            '--seed', '3000000019', '--seconds', '2',\n"
        "            '--trace', '1', '--rehearse']\n"
        "runpy.run_path('chipbench/run.py', run_name='__main__')\n"
        % str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                       env=env, text=True, capture_output=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu"
    assert set(last["metrics"]) == set(NAMES)
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert m["step_interval_ms.train"] > m["exe_step_ms.train"] > 0
    assert 0 <= m["step_stall_pct.train"] < 100
    assert "3 that touch the traced stretch left out" in p.stdout
    assert "(2 traced left out)" in p.stdout
