"""What a Mamba-2 mixer runs beside its matmuls and the scan kernels,
as a share of device time: the train step's ops scoped to the Program
ops ``ssm_conv`` (the three causal depthwise convolutions over x, B_t
and C_t with their bias and SiLU), ``ssm_dt`` (``softplus`` of the step
size), ``gated_group_norm`` (the gate, then the norm over each group of
channels) and, under ``ssd_scan``, everything that is NOT one of its
two kernels (XLA's: the padding, the steps' running sums and their
turned copies, ``A = -exp(A_log)``, ``D x``, the running sums'
cotangent summed back), forward, recomputed and backward:
bandwidth-bound passes over ``[T, d_inner]`` between the matmuls. Over
busy time (chip 0). The log line gives the four apart, and the scan
kernels' own share beside them. XLA gives a fusion the scope of its
first instruction, so an op it fuses into a neighbour counts where the
fusion's root lies. None where the step has no op scoped ``ssd_scan``
(another model's ``ssm_conv`` is its own reader's)."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
SCAN = "ssd_scan"
GLUE = ("ssm_conv", "ssm_dt", "gated_group_norm")


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    parts = dict.fromkeys(GLUE + (SCAN, "kernels"), 0.0)
    scanned = False
    for op in window["ops"]:
        if op["program"] != program:
            continue
        scope = spans.scope_type(op["scope"])
        scanned = scanned or scope == SCAN
        if scope == SCAN and op["kernel"]:
            scope = "kernels"
        if scope in parts:
            parts[scope] += op["dur"]
    if not scanned:
        return None
    kernels = parts.pop("kernels")
    spans.say("ssd_glue_dev_share_pct: %s; the scan kernels beside them "
              "%.6f s (%.2f%% of busy time)" % (
                  ", ".join("%s%s %.6f s" % (
                      scope, "'s XLA ops" if scope == SCAN else "",
                      parts[scope]) for scope in GLUE + (SCAN,)),
                  kernels, spans.busy_share_pct(run, kernels)))
    return spans.busy_share_pct(run, sum(parts.values()))
