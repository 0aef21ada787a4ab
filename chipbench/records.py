"""What several metric readers share: views of a run's records.

``run`` is what a driver returns plus the cell's files: ``requests``
(every request submitted, with its ``segment``; times in seconds from
the window's start; the metrics cover segment ``window``), ``engine``
(counter deltas over the window), ``train`` (the window's steps and
times, and ``counters``: ``{name: list of numbers}``, what the program
counted on the device over the run as the architecture's
``program_counters`` read it once after the window; ``{}`` where it
exports none), ``trace`` (the reduced trace, in a traced run),
``config``, ``traffic``, ``peaks``, ``chips``, ``setup_s`` and
``setup_phases`` (seconds from the process's start to ``entered``, the
driver's ``run``, and to each set-up phase the driver stamps).
"""

import numpy as np


def window(run):
    """The requests due inside the window."""
    return [r for r in run.get("requests", ())
            if r.get("segment", "window") == "window"]


def done(run):
    """The window's requests that finished."""
    return [r for r in window(run) if r["done"]]


def ttft_ms(run):
    """Time to first token of each finished request of the window, from
    when it was DUE."""
    return [1e3 * (r["first"] - r["due"]) for r in done(run)]


def percentile(values, q):
    return float(np.percentile(values, q)) if len(values) else None


def module_runs(run, substring):
    """Device seconds of every traced run of the jitted programs whose
    name holds ``substring``."""
    trace = run.get("trace")
    if not trace:
        return []
    return [d for name, durs in trace["modules"].items()
            if substring in name for d in durs]


def idle_pct(run):
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
