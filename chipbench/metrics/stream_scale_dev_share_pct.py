"""What a model's published multipliers cost as passes of their own, as
a share of device time: the train step's ops scoped to the Program op
``scale`` (granite's four: the embedding's rows times
``embedding_multiplier``, each sublayer's result times
``residual_multiplier`` before it joins the stream, the logits over
``logits_scaling``; the scores' ``attention_multiplier`` is inside the
flash kernels and costs nothing of its own), forward, recomputed and
backward. Over busy time (chip 0). Unfused, each is a pass over ``[T,
d]`` (the logits' over ``[T, V]``); XLA gives a fusion the scope of its
first instruction, so a multiplier fused into a neighbour counts where
the fusion's root lies, and the share falls to what is left under the
scope. The log line gives the seconds by HLO op kind. A step in which
no device op carries the scope reads 0.0 (all fused away, or a program
with no multiplier: the metric lists the cells that have them); None
only where there is no traced window."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
SCOPE = "scale"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, steps = spans.step_program(window)
    if not steps:
        return None
    kinds = {}
    for op in window["ops"]:
        if op["program"] == program \
                and spans.scope_type(op["scope"]) == SCOPE:
            kinds[op["kind"]] = kinds.get(op["kind"], 0.0) + op["dur"]
    total = sum(kinds.values())
    spans.say("stream_scale_dev_share_pct: %.6f s under the scope %r in "
              "%d steps (%s)" % (total, SCOPE, steps, ", ".join(
                  "%s %.6f" % kv for kv in sorted(
                      kinds.items(), key=lambda kv: -kv[1])[:6]) or
                  "no op carries it: fused into its neighbours"))
    return spans.busy_share_pct(run, total)
