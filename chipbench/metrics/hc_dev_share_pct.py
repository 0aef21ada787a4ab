"""The hyper-connections as a share of device time: the train step's
ops scoped to the Program's ``hyper_connection`` op, forward,
recomputed and backward: the stream's norm and projection onto the
coefficients, the Sinkhorn-Knopp rounds, ``H_pre X``, ``H_res X +
H_post^T y``, the copy in and the sum out. Over busy time (chip 0). The
log line gives the Sinkhorn rounds (ops under ``sinkhorn`` inside the
scope) and the rest (the projection and the stream's mixes) apart, and
the rest by HLO op kind. None where the step has no op scoped
``hyper_connection``."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
OP = "hyper_connection"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    mine = [op for op in window["ops"] if op["program"] == program
            and spans.scope_type(op["scope"]) == OP]
    if not mine:
        return None
    rounds = sum(op["dur"] for op in mine
                 if "/sinkhorn/" in (op["op_name"] or ""))
    kinds = {}
    for op in mine:
        if "/sinkhorn/" not in (op["op_name"] or ""):
            kinds[op["kind"]] = kinds.get(op["kind"], 0.0) + op["dur"]
    total = rounds + sum(kinds.values())
    spans.say("hc_dev_share_pct: %.6f s, of which the Sinkhorn rounds "
              "%.6f and the projection and the stream's mixes %.6f (%s)" % (
                  total, rounds, total - rounds, ", ".join(
                      "%s %.6f" % kv for kv in sorted(
                          kinds.items(), key=lambda kv: -kv[1])[:8])))
    return spans.busy_share_pct(run, total)
