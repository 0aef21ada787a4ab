"""Attention's glue as a share of device time: the train step's ops
scoped to the Program's ``sp_attention`` op, forward and backward, that
are NOT Pallas kernels (the row statistics' hand-over between the flash
kernels: broadcasts, slices, the ``delta`` reduction, copies), over busy
time (chip 0). The log line splits it by HLO op kind. None where the
step has no op scoped ``sp_attention``."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
OP = "sp_attention"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    if not spans.device_time(window, program, scope_type=OP):
        return None
    glue = spans.device_time(window, program, scope_type=OP, kernel=False)
    kinds = sorted({op["kind"] for op in window["ops"]
                    if op["program"] == program and not op["kernel"]
                    and spans.scope_type(op["scope"]) == OP})
    spans.say("attn_glue_dev_share_pct: %.6f s outside the kernels (%s)"
              % (glue, ", ".join("%s %.6f" % (k, spans.device_time(
                  window, program, scope_type=OP, kernel=False, kind=k))
                  for k in kinds) or "no such op"))
    return spans.busy_share_pct(run, glue)
