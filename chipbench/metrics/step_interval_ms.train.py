"""The pace of the window's steps from inside the program: the median
of ``t_enter[i + 1] - t_enter[i]`` over the ``exe.step`` rows of the
program's step ledger (``paddle_tpu.trace.steps``), ALL of the window's
steps and with no profiler; the intervals that touch the traced stretch
are left out. With the host ahead of the device it is the pace the
device sets, and ``tokens_per_step`` over it is the rate the run would
have read with no stall: the log line prints that beside the window's
own rate. None where the program keeps no ledger or its rows are not
the window's."""
import statistics

from chipbench import spans, steps

UNIT, SOURCE = "ms", "program_span"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    window = steps.of(run)
    if window is None:
        return None
    t = run["train"]
    secs = [iv["seconds"] for iv in window["intervals"]]
    q1, _, q3 = statistics.quantiles(secs, n=4)
    spans.say(
        "step_interval_ms.train: %d intervals (%d that touch the traced "
        "stretch left out): median %.3f ms, min %.3f, quartiles %.3f .. "
        "%.3f, max %.3f; %.1f tokens/s at that pace, %.1f over the whole "
        "window" % (
            len(secs), window["left_out"], 1e3 * window["median_s"],
            1e3 * min(secs), 1e3 * q1, 1e3 * q3, 1e3 * max(secs),
            t["tokens_per_step"] / window["median_s"],
            t["steps"] * t["tokens_per_step"] / t["window_s"]))
    return 1e3 * window["median_s"]
