"""What a delta-rule mixer runs beside its matmuls and the rule, as a
share of device time: the train step's ops scoped to the Program ops
``ssm_conv`` (the three causal depthwise convolutions and their SiLU),
``l2_norm_scale`` (a head's query and key over their norm),
``delta_gates`` (``beta`` and the decay's log from two ``[T, H]``
projections) and ``gated_rms_norm`` (the norm over each head and the
output gate), forward, recomputed and backward: bandwidth-bound passes
over ``[T, H d_k]`` and ``[T, H d_v]`` between the matmuls. Over busy
time (chip 0). The log line gives the four apart. XLA gives a fusion
the scope of its first instruction, so an op it fuses into a neighbour
counts where the fusion's root lies. None where the step has no op
scoped ``gated_delta_rule`` (another model's ``ssm_conv`` is its own
reader's) or none of the four scopes."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
RULE = "gated_delta_rule"
GLUE = ("ssm_conv", "l2_norm_scale", "delta_gates", "gated_rms_norm")


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    parts, rule = dict.fromkeys(GLUE, 0.0), False
    for op in window["ops"]:
        if op["program"] != program:
            continue
        scope = spans.scope_type(op["scope"])
        rule = rule or scope == RULE
        if scope in parts:
            parts[scope] += op["dur"]
    if not rule or not sum(parts.values()):
        return None
    spans.say("delta_glue_dev_share_pct: " + ", ".join(
        "%s %.6f s" % (scope, parts[scope]) for scope in GLUE))
    return spans.busy_share_pct(run, sum(parts.values()))
