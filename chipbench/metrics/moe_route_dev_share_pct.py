"""The router's part of the expert layer as a share of device time: the
train step's ops scoped ``route`` under the Program's
``routed_experts`` op (the scope ``parallel/moe.routed_experts`` opens
round the float32 router's matmul, its softmax and top-k, the count of
rows an expert took and the sort of the pairs), forward, recomputed and
backward. Where the router reads the layer's input
(``layers.routed_experts(..., router_input=...)``) this is the part of
the expert layer that stands before attention and waits on nothing of
it. Over busy time (chip 0); ``moe_glue_dev_share_pct`` holds it and
the rest of the layer's glue. The log line gives the router's matmul,
the top-k and the sort apart, and what is left. None where the step
has no op under that scope (a program from before PR 46 opens none)."""
import re

from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
OP = "routed_experts"
_ROUTE = re.compile(r"(^|[/(])route[)/]")
# the primitive an op's name ends in -> the part it belongs to
PARTS = {"dot_general": "matmul", "top_k": "top_k", "sort": "sort"}


def part_of(op_name):
    """``matmul`` / ``top_k`` / ``sort`` / ``other`` for one device op,
    by the primitive its FIRST joined name ends in
    (``.../route/jit(argsort)/sort:``)."""
    last = (op_name or "").split(";")[0].rstrip("/:").rsplit("/", 1)[-1]
    return PARTS.get(last.split("[")[0], "other")


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    parts = {}
    for op in window["ops"]:
        if (op["program"] == program and spans.scope_type(op["scope"]) == OP
                and _ROUTE.search(op["op_name"] or "")):
            part = part_of(op["op_name"])
            parts[part] = parts.get(part, 0.0) + op["dur"]
    total = sum(parts.values())
    if not total:
        return None
    spans.say("moe_route_dev_share_pct: %.6f s under the scope route (%s)"
              % (total, ", ".join("%s %.6f" % (p, parts.get(p, 0.0))
                                  for p in ("matmul", "top_k", "sort",
                                            "other"))))
    return spans.busy_share_pct(run, total)
