"""The executor's whole host time a step, from inside and untraced: the
median of ``t_exit - t_enter`` over the window's ``exe.step`` rows of
the program's step ledger (``paddle_tpu.trace.steps``), the traced
steps left out. The log line gives each phase's median and the self
time (the step less its phases). The inside twin of
``step_host_ms.train`` (the driver's clock round ``exe.run``, which
holds this and the call) and of ``exe_self_ms.train`` (the traced steps
under the profiler, which slows the host). None where the program keeps
no ledger or its rows are not the window's."""
import statistics

from chipbench import spans, steps

UNIT, SOURCE = "ms", "program_span"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    window = steps.of(run)
    if window is None:
        return None
    rows = [r for r in window["rows"] if r["step"] not in window["traced"]]
    whole = [r["t_exit"] - r["t_enter"] for r in rows]
    median = 1e3 * statistics.median(whole)
    names = sorted({n for r in rows for n in r["phases"]})
    parts = ["%s %.3f" % (n, 1e3 * statistics.median(
        r["phases"].get(n, 0.0) for r in rows)) for n in names]
    parts.append("self %.3f" % (1e3 * statistics.median(
        w - sum(r["phases"].values()) for w, r in zip(whole, rows))))
    spans.say("exe_step_ms.train: %d steps (%d traced left out): median "
              "%.3f ms, max %.3f; medians by phase, ms: %s; %d of them "
              "compiled (fresh)" % (
                  len(rows), len(window["rows"]) - len(rows), median,
                  1e3 * max(whole), ", ".join(parts),
                  sum(r["fresh"] for r in rows)))
    return median
