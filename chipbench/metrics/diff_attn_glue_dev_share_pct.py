"""What differential attention runs round its flash kernels, as a share
of device time: the train step's ops scoped to the Program op
``diff_attn`` (``lam``, the subtraction ``a1 - lam a2``, the RMSNorm
over each head's 128 and the ``1 - lam0`` scale) and, under the op
``diff_attention``, everything that is NOT a flash kernel: the relayout
between the projections and the kernels (the query with the lanes of
the other head of each pair zeroed, once a softmax, and the two calls'
``dq``, ``dk`` and ``dv`` added), forward, recomputed and backward. Over busy time (chip 0). The log line gives
the two apart, and the flash kernels' seconds by the layer's kind, from
the scope the op opens under its own (``window``, ``full``, ``cross``).
None where the step has neither scope."""
import re

from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
JOIN, CALL = "diff_attn", "diff_attention"
_KIND = re.compile(r"(?:^|[/(])(window|full|cross)[)/]")


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    join = relayout = 0.0
    kernels = {}
    for op in window["ops"]:
        if op["program"] != program:
            continue
        scope = spans.scope_type(op["scope"])
        if scope == JOIN:
            join += op["dur"]
        elif scope == CALL and not op["kernel"]:
            relayout += op["dur"]
        elif scope == CALL:
            kind = _KIND.search(op["op_name"] or "")
            kind = kind.group(1) if kind else "?"
            kernels[kind] = kernels.get(kind, 0.0) + op["dur"]
    if not join + relayout + sum(kernels.values()):
        return None
    spans.say("diff_attn_glue_dev_share_pct: the join %.6f s, the "
              "relayout round the kernels %.6f s; the flash kernels "
              "beside them: %s" % (join, relayout, ", ".join(
                  "%s %.6f s" % kv for kv in sorted(kernels.items()))))
    return spans.busy_share_pct(run, join + relayout)
