"""The gated short convolution as a share of device time: the train
step's ops scoped to the Program op ``gated_short_conv``, forward, the
region's second forward and backward: the two gates and the taps
between a conv layer's two projections (``ops/short_conv.py``:
``jax.numpy``, no kernel), whatever XLA makes of them. Over busy time
(chip 0). None where the step has no op scoped ``gated_short_conv``,
as every program before PR 49 and every model without a conv layer."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
OP = "gated_short_conv"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    kinds = {}
    for op in window["ops"]:
        if op["program"] == program and spans.scope_type(op["scope"]) == OP:
            kinds[op["kind"]] = kinds.get(op["kind"], 0.0) + op["dur"]
    if not kinds:
        return None
    total = sum(kinds.values())
    spans.say("short_conv_dev_share_pct: %.6f s (%s)" % (total, ", ".join(
        "%s %.6f" % kv for kv in sorted(kinds.items(),
                                        key=lambda kv: -kv[1])[:8])))
    return spans.busy_share_pct(run, total)
