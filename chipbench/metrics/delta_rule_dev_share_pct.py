"""The gated delta rule as a share of device time: the train step's ops
scoped to the Program op ``gated_delta_rule``, forward, the region's
second forward and backward: the chunk-local products, the triangular
system, the walk over the chunks' states and autodiff's transpose of
them (``ops/delta_rule.py``: ``jax.numpy``, no kernel), whatever XLA
makes of them. Over busy time (chip 0). None where the step has no op
scoped ``gated_delta_rule``, as every program before PR 53 and every
model without a linear-attention layer."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
OP = "gated_delta_rule"


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
    spans.say("delta_rule_dev_share_pct: %.6f s (%s)" % (total, ", ".join(
        "%s %.6f" % kv for kv in sorted(kinds.items(),
                                        key=lambda kv: -kv[1])[:8])))
    return spans.busy_share_pct(run, total)
