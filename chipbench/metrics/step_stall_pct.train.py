"""The share of the window that stalls took: the seconds by which
intervals over 1.5 x the median exceed the median, over the seconds of
the intervals read (the window's less those that touch the traced
stretch, the benchmark's own stall); from the ``exe.step`` rows of the
program's step ledger (``paddle_tpu.trace.steps``). A steady run reads
0. The log line gives the five largest: the step that opened the
interval, its seconds, what held the host (the phase of the step, its
``self`` time, or ``outside``, the driver's ``block_until_ready``, that
rose most over its own median), ``device_waited`` at the next entry
(True: the device had run dry and the HOST was late; False: the device,
or the runtime under it, was still at work), ``outside`` before the two
entries after that (both near nothing: their fetches were done already,
completions had piled up, the host was kept from hearing of them; a
step's time: the device itself was slow) and every collection and
compile-log row that ended inside. None where the program keeps no
ledger or its rows are not the window's."""
from chipbench import spans, steps

UNIT, SOURCE = "%", "program_span"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    window = steps.of(run)
    if window is None:
        return None
    found = steps.stalls(window)
    lost = sum(s["excess"] for s in found)
    spans.say("step_stall_pct.train: %d of %d intervals over 1.5 x the "
              "median of %.3f ms, %.3f s over it in all (%d intervals "
              "that touch the traced stretch left out)" % (
                  len(found), len(window["intervals"]),
                  1e3 * window["median_s"], lost, window["left_out"]))
    for s in found[:5]:
        spans.say(
            "step_stall_pct.train: step %s: %.3f s, held by %s (%.3f s), "
            "device_waited at the next entry %s, outside before the two "
            "entries after it %s s; ended inside: %s" % (
                s["step"], s["seconds"], s["held"], s["held_s"],
                s["device_waited"],
                " ".join("%.4f" % a for a in s["after"]), "; ".join(
                    "%s %.3f s" % (e["what"].strip(), e["seconds"])
                    for e in s["events"]) or "nothing recorded"))
    return 100.0 * lost / window["kept_s"]
