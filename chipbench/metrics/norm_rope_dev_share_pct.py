"""RMSNorm and the rotary embedding as a share of device time: the
train step's ops scoped to the Program's ``rms_norm``, ``rope`` or
``qk_norm_rope`` ops, forward and backward, kernels included (a Pallas
kernel keeps its Program op's scope): what the norms of the stream, the
QK-norm of the heads and RoPE cost together, whichever ops the model
expresses them with, so the reading compares a program that relays its
operands for the heads' view with one that does not. Over busy time
(chip 0). None where the step has none of the three."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
OPS = ("rms_norm", "rope", "qk_norm_rope")


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    kinds = {}
    for op in window["ops"]:
        if op["program"] == program and spans.scope_type(op["scope"]) in OPS:
            kinds[op["kind"]] = kinds.get(op["kind"], 0.0) + op["dur"]
    if not kinds:
        return None
    total = sum(kinds.values())
    spans.say("norm_rope_dev_share_pct: %.6f s (%s)" % (total, ", ".join(
        "%s %.6f" % kv for kv in sorted(kinds.items(),
                                        key=lambda kv: -kv[1])[:8])))
    return spans.busy_share_pct(run, total)
