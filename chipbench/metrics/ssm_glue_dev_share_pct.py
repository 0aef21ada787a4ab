"""What a state-space mixer and a gated memory unit run beside their
matmuls and the scan kernels, as a share of device time: the train
step's ops scoped to the Program ops ``ssm_conv`` (the causal depthwise
convolution and its SiLU), ``ssm_gate`` and ``gmu_gate`` (``y *
silu(z)``, ``memory * silu(g)``), ``ssm_dt`` (``softplus`` of the step
size) and, under ``selective_scan``, everything that is NOT one of its
two kernels (XLA's: the padding, B_t and C_t broadcast over the lanes,
their gradients summed back over them, ``dD``, ``A = -exp(A_log)``),
forward, recomputed and backward: bandwidth-bound passes over ``[T,
d_inner]`` between the matmuls. Over busy time (chip 0). The log line
gives the convolution, the gates and the rest apart, and the scan
kernels' own share beside them. XLA gives a fusion the scope of its
first instruction, so an op it fuses into a neighbour counts where the
fusion's root lies. None where the step has none of the scopes."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
CONV, GATES, STEP, SCAN = (("ssm_conv",), ("ssm_gate", "gmu_gate"),
                           ("ssm_dt",), "selective_scan")


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    parts = {"conv": 0.0, "gates": 0.0, "rest": 0.0, "kernels": 0.0}
    for op in window["ops"]:
        if op["program"] != program:
            continue
        scope = spans.scope_type(op["scope"])
        part = ("conv" if scope in CONV else "gates" if scope in GATES
                else "rest" if scope in STEP else None)
        if scope == SCAN:
            part = "kernels" if op["kernel"] else "rest"
        if part:
            parts[part] += op["dur"]
    if not sum(parts.values()):
        return None
    glue = parts["conv"] + parts["gates"] + parts["rest"]
    spans.say("ssm_glue_dev_share_pct: the convolution %.6f s, the gates "
              "%.6f s, the step size and the scan's XLA ops %.6f s; the "
              "scan kernels beside them %.6f s (%.2f%% of busy time)" % (
                  parts["conv"], parts["gates"], parts["rest"],
                  parts["kernels"],
                  spans.busy_share_pct(run, parts["kernels"])))
    return spans.busy_share_pct(run, glue)
