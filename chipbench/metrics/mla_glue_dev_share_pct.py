"""Latent attention's glue as a share of device time: the train step's
ops scoped to the Program's ``mla_attention`` op, forward, recomputed
and backward, that are NOT Pallas kernels: the rotary embedding of
``q_pe`` and ``k_pe``, the shared key repeated to a lane tile and its
gradient folded back, any slice, concatenation or relayout between the
projections and the flash kernels. Over busy time (chip 0). The log
line splits it by HLO op kind. None where the step has no op scoped
``mla_attention``."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
OP = "mla_attention"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    mine = [op for op in window["ops"] if op["program"] == program
            and spans.scope_type(op["scope"]) == OP]
    if not mine:
        return None
    kinds = {}
    for op in mine:
        if not op["kernel"]:
            kinds[op["kind"]] = kinds.get(op["kind"], 0.0) + op["dur"]
    glue = sum(kinds.values())
    spans.say("mla_glue_dev_share_pct: %.6f s outside the kernels (%s)" % (
        glue, ", ".join("%s %.6f" % kv for kv in sorted(
            kinds.items(), key=lambda kv: -kv[1])[:8]) or "no such op"))
    return spans.busy_share_pct(run, glue)
