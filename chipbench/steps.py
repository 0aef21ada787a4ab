"""The window's steps from INSIDE the program, with no profiler.

``paddle_tpu.trace.steps()`` is the program's step ledger (PR 36): one
row for every ``exe.step`` on ``time.perf_counter()``, always, with its
entry and exit, the seconds of each phase inside it, the seconds since
the previous root's exit (``outside``: here the driver's
``block_until_ready``), whether the previous step's fetch was done at
entry (``device_waited``), and collections of 1 ms or more as event
rows among them. ``of(run)`` cuts it to the measured window; the three
readers ``step_interval_ms.train``, ``step_stall_pct.train`` and
``exe_step_ms.train`` read that. ``run.py`` reads per-layer metrics in
traced runs only, so they report the traced runs' windows: ALL of each
window, where the profiler's readers see the ``trace_steps`` traced
ones.

The window's rows are the last ``train.steps`` rows of ``exe.step``
(nothing runs a step after the window). From the first entry to the
last exit they must span ``train.window_s`` less what the window's last
``block_until_ready`` waits for, which is no row's: the driver's
``IN_FLIGHT`` steps and the one the host was waiting on when it
dispatched the last (a step's time varies, so one more step of room,
and never under ``ROOM_S``); or the rows are not the window's and the
readers return None.

An interval is ``t_enter[i + 1] - t_enter[i]``. The traced stretch is
the benchmark's own stall (the profiler's start and stop, and the
``block_until_ready`` round them): the intervals with a traced step at
either end are left out, and the traced steps are told by number, which
a row shares with its ``exe.step`` annotation in the trace.

A tree with no ring (the parent of the PR that added it) gives None
everywhere, and the metrics are left out of the line.
"""

import statistics

from chipbench import spans
from chipbench.drivers import train_steps

ROOT = "exe.step"
OVER = 1.5          # an interval over this many medians is a stall
ROOM_S = 0.5        # the least room at the window's end (a rehearsal's
                    # steps take milliseconds on a host that others share)


def ledger():
    """``paddle_tpu.trace.steps``, or None where the program keeps no
    step ledger."""
    try:
        from paddle_tpu import trace
    except ImportError:
        return None
    return getattr(trace, "steps", None)


def traced_steps(run):
    """The numbers of the steps that ran under the profiler: the
    ``step`` of every ``exe.step`` annotation in the trace; None where a
    run that traced steps has no such annotation to tell them by."""
    if not run["train"].get("traced_steps"):
        return set()
    window = spans.of(run)
    found = {int(s["args"]["step"]) for s in (window or {}).get("host", ())
             if s["name"] == ROOT and "step" in s["args"]}
    return found or None


def of(run):
    """The measured window from the step ledger, read once and kept on
    ``run``: ``rows`` (the window's step rows), ``intervals`` (``{"i",
    "step", "seconds", "t0", "t1", "device_waited", "pieces"}`` for each
    kept interval, by the row that opened it and ``device_waited`` of
    the row that closed it: ``pieces`` is where the host was, by phase
    of that step, its ``self`` time, and ``outside`` after it),
    ``traced`` (the numbers
    of the steps under the profiler), ``left_out`` (the count of
    intervals that touch the traced stretch), ``median_s``, ``kept_s``,
    ``events`` (the collections and the compile log's rows, ``{"what",
    "end", "seconds"}``); None, with the reason said, where there is no
    ledger or its rows are not the window's."""
    if "step_window" not in run:
        run["step_window"] = _window(run)
    return run["step_window"]


def _window(run):
    read = ledger()
    if read is None:
        spans.say("step ledger: the program keeps none "
                  "(no paddle_tpu.trace.steps)")
        return None
    t = run["train"]
    rows = read(ROOT)[-t["steps"]:]
    if len(rows) < max(t["steps"], 3) or any(
            r["t_exit"] is None for r in rows):
        spans.say("step ledger: %d closed rows of %s for a window of %d "
                  "steps" % (len(rows), ROOT, t["steps"]))
        return None
    enters = [r["t_enter"] for r in rows]
    gaps = [b - a for a, b in zip(enters, enters[1:])]
    pace = statistics.median(gaps)
    short = t["window_s"] - (rows[-1]["t_exit"] - rows[0]["t_enter"])
    room = max((train_steps.IN_FLIGHT + 2) * pace, ROOM_S)
    if not -pace <= short <= room:
        spans.say("step ledger: its last %d rows span %.3f s and the "
                  "window %.3f s (a step is %.3f s): they are not the "
                  "window's" % (len(rows), t["window_s"] - short,
                                t["window_s"], pace))
        return None
    traced = traced_steps(run)
    if traced is None:
        spans.say("step ledger: the run traced %d steps and the trace "
                  "holds no %s annotation to tell them by"
                  % (t["traced_steps"], ROOT))
        return None
    intervals = []
    for i, (a, b) in enumerate(zip(rows, rows[1:])):
        if a["step"] in traced or b["step"] in traced:
            continue
        pieces = dict(a["phases"])
        pieces["self"] = (a["t_exit"] - a["t_enter"]
                          - sum(a["phases"].values()))
        pieces["outside"] = b["outside"]
        intervals.append({"i": i, "step": a["step"], "seconds": gaps[i],
                          "pieces": pieces,
                          "device_waited": b["device_waited"],
                          "t0": a["t_enter"], "t1": b["t_enter"]})
    if not intervals:
        spans.say("step ledger: every interval touches the traced "
                  "stretch")
        return None
    lo, hi = rows[0]["t_enter"], rows[-1]["t_exit"]
    events = [{"what": "gc gen %d" % e["generation"], "end": e["end"],
               "seconds": e["seconds"]} for e in read()
              if "event" in e and lo <= e["end"] <= hi]
    events += [{"what": "%s %s" % (c["what"], c.get("fun_name") or ""),
                "end": c["end"], "seconds": c["seconds"]}
               for c in spans.compile_log() or ()
               if lo <= c["end"] <= hi]
    kept = [iv["seconds"] for iv in intervals]
    return {"rows": rows, "intervals": intervals,
            "traced": traced,
            "left_out": len(gaps) - len(intervals),
            "median_s": statistics.median(kept), "kept_s": sum(kept),
            "events": sorted(events, key=lambda e: e["end"])}


def stalls(window):
    """The intervals over ``OVER`` x the median, largest first, each
    with its ``excess`` over the median, ``held`` (the piece of it that
    rose most over that piece's median: a phase of the step, its
    ``self`` time, or ``outside``), ``events`` (what ended inside it)
    and ``after`` (the ``outside`` of the next two entries: where both
    are near nothing the fetches they waited on were done already, so
    completions had piled up and the device had run through its queue
    while the host was kept from hearing of it; after a stall of the
    device's own they are a step's time)."""
    ivs, rows = window["intervals"], window["rows"]
    usual = {}
    for name in {n for iv in ivs for n in iv["pieces"]}:
        usual[name] = statistics.median(
            iv["pieces"].get(name) or 0.0 for iv in ivs)
    out = []
    for iv in ivs:
        if iv["seconds"] <= OVER * window["median_s"]:
            continue
        rise = {n: (s or 0.0) - usual[n] for n, s in iv["pieces"].items()}
        held = max(rise, key=rise.get)
        out.append(dict(
            iv, excess=iv["seconds"] - window["median_s"], held=held,
            held_s=iv["pieces"][held],
            after=[r["outside"] for r in rows[iv["i"] + 2:iv["i"] + 4]],
            events=[e for e in window["events"]
                    if iv["t0"] < e["end"] <= iv["t1"]]))
    return sorted(out, key=lambda s: -s["excess"])
