"""The expert layer's glue as a share of device time: the train step's
ops scoped to the Program's ``routed_experts`` op, forward and
backward: the float32 router, top-k, the sort, the gathers in and out,
the weighted sums, the casts of the experts' weights. Its grouped
matmuls are not among them (XLA strips their scope;
``expert_matmul_roof_pct`` reads them by name). Over busy time (chip
0). None where the step has no op scoped ``routed_experts``."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
OP = "routed_experts"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    glue = spans.device_time(window, program, scope_type=OP)
    if not glue:
        return None
    kinds = {}
    for op in window["ops"]:
        if op["program"] == program and spans.scope_type(op["scope"]) == OP:
            kinds[op["kind"]] = kinds.get(op["kind"], 0.0) + op["dur"]
    spans.say("moe_glue_dev_share_pct: %.6f s (%s)" % (glue, ", ".join(
        "%s %.6f" % kv for kv in sorted(kinds.items(),
                                        key=lambda kv: -kv[1])[:8])))
    return spans.busy_share_pct(run, glue)
