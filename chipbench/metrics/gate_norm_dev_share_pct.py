"""Attention's output gate and the stream's RMSNorms as a share of
device time: the train step's ops scoped to the Program's
``sigmoid_mul`` op (``attn * sigmoid(g)``; the gate's projection is a
``mul`` like the others and not in it) and to its ``rms_norm`` ops (the
four norms of a layer, before and after each sublayer, and the final
norm; QK-norm is ``qk_norm_rope``'s and not in it), forward, recomputed
and backward: bandwidth-bound passes over ``[T, d]`` and ``[T, H D]``
between the matmuls. Over busy time (chip 0). The log line gives the
gate and the norms apart, each by HLO op kind. XLA gives a fusion the
scope of its first instruction, so a norm or a gate that XLA fuses into
a neighbour counts where the fusion's root lies: in the cell's first
trace the gate read 0 s under its own scope (it rides in the output
projection's matmul fusion; my chip run, PR 38). None where the step
has neither scope."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
GATE, NORM = "sigmoid_mul", "rms_norm"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    parts = {GATE: {}, NORM: {}}
    for op in window["ops"]:
        kinds = parts.get(spans.scope_type(op["scope"]))
        if kinds is not None and op["program"] == program:
            kinds[op["kind"]] = kinds.get(op["kind"], 0.0) + op["dur"]
    total = sum(sum(kinds.values()) for kinds in parts.values())
    if not total:
        return None
    top = lambda kinds: ", ".join("%s %.6f" % kv for kv in sorted(
        kinds.items(), key=lambda kv: -kv[1])[:6])
    spans.say("gate_norm_dev_share_pct: the gate %.6f s (%s); the norms "
              "%.6f s (%s)" % (sum(parts[GATE].values()), top(parts[GATE]),
                               sum(parts[NORM].values()), top(parts[NORM])))
    return spans.busy_share_pct(run, total)
