"""The dense matmuls' share of the compute roofline, counted by the
PROGRAM: the useful FLOPs of every ``mul`` / ``matmul`` row of the train
step's table of the op ledger (``paddle_tpu.trace.ops``: 2 MKN forward
and 2 MKN more for each gradient the step takes, ``grads``; a region's
second forward is credited nothing) times the traced steps, over the
peak bf16 rate, over the device time of the ops scoped to those rows in
all three passes (chip 0; ``chipbench/oplog.py``). No architecture
file's arithmetic is in it, so it reads in every training cell, and the
experts' grouped matmuls, which are no ``mul``, are not in it. XLA fuses
elementwise neighbours into a matmul's fusion (bias, Adam's update of
the weight, the cross-entropy's gradient into the head's): their time
is in the divisor, so this reads low rather than high.

The log lines give the products by FAMILY (the weight's name with every
run of digits folded to ``#``, ``K x N``, ``M``, the count of ops):
forward / second forward / backward ms a step and the share of the peak
each pass reaches (a second forward by what it executes), the family
furthest from the peak first, by the ms a step it spends over the time
the peak would take; at most twelve; then the books."""
from chipbench import oplog, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "train executor", "tokens_per_s"
LINES = 12


def read(run):
    value, fams, window = oplog.roof_pct(run, oplog.PASSES)
    if value is None:
        return None
    steps, peak = window["steps"], run["peaks"]["flops_bf16"]
    ms = lambda s: 1e3 * s / steps
    share = lambda f, p: 100.0 * steps * f["flops"][p] / peak / \
        f["seconds"][p] if f["seconds"][p] else 0.0
    room = lambda f: sum(f["seconds"].values()) - steps * sum(
        f["flops"].values()) / peak
    worst = sorted(fams.items(), key=lambda kv: -room(kv[1]))
    for (name, m, k, n), f in worst[:LINES]:
        spans.say(
            "dense_matmul_roof_pct: %s %d x %d, M %d, %d op(s): forward "
            "%.3f ms a step at %.1f%% of the peak, second forward %.3f "
            "at %.1f%%, backward %.3f at %.1f%%; %.3f ms over the "
            "peak's time" % (
                name, k, n, m, f["ops"], ms(f["seconds"]["fwd"]),
                share(f, "fwd"), ms(f["seconds"]["second"]),
                share(f, "second"), ms(f["seconds"]["bwd"]),
                share(f, "bwd"), ms(room(f))))
    scoped = sum(op["dur"] for op in window["ops"] if op["row"]
                 and op["row"]["type"] in oplog.DENSE)
    spans.say(
        "dense_matmul_roof_pct: %d families (%d not shown) hold %.6f s "
        "and the ops scoped %s %.6f s in %d steps; %.3f TFLOP a step "
        "useful; %d device ops of the step carry a scope with no row" % (
            len(fams), max(0, len(fams) - LINES),
            sum(sum(f["seconds"].values()) for f in fams.values()),
            " / ".join(oplog.DENSE), scoped, steps,
            1e-12 * sum(f["flops"]["fwd"] + f["flops"]["bwd"]
                        for f in fams.values()), window["unjoined"]))
    return value
