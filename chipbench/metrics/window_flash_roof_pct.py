"""The WINDOW layers' flash kernels' share of the compute roofline: the
band's useful FLOPs of the sliding-window layers in the traced steps
(the architecture's ``window_flash_flops_per_step``, from shapes: a
query's own key and the window - 1 before it, 14 D a score, forward
and backward) over the peak bf16 rate, over the device time on chip 0
of the kernels named ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``
and ``flash_bwd`` whose op name carries the scope ``window`` (the
Program op ``causal_attention`` opens it under its own scope where its
layer has a window, ``full`` where it has none). Under per-layer
recompute the forward kernel runs twice a step and its useful FLOPs are
counted once. Masked tiles on the band's two edges are in the time and
not in the count (``window_scores_over_useful`` says how many). The log
line gives the full layers' kernels beside them. None where the
architecture states no window layers' FLOPs or no kernel ran under the
scope."""
import re

from chipbench import cells, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd")
_WINDOW = re.compile(r"(^|[/(])window[)/]")


def read(run):
    window = spans.of(run)
    arch = cells.load_arch(run["config"]["arch"])
    if not window or not hasattr(arch, "window_flash_flops_per_step"):
        return None
    _, steps = spans.step_program(window)
    flash = [op for op in window["ops"]
             if op["kernel"] and op["kind"] in KERNELS]
    mine = [op for op in flash if _WINDOW.search(op["op_name"] or "")]
    seconds = sum(op["dur"] for op in mine)
    if not steps or not seconds:
        return None
    t = run["train"]
    flops = steps * arch.window_flash_flops_per_step(
        run["config"], t["batch"], t["seq_len"]) / run["chips"]
    by_kind = {}
    for op in mine:
        by_kind[op["kind"]] = by_kind.get(op["kind"], 0.0) + op["dur"]
    spans.say("window_flash_roof_pct: %.6f s in the window layers' flash "
              "kernels (%s), %.6f s in the other layers', in %d steps" % (
                  seconds, ", ".join("%s %.6f" % kv
                                     for kv in sorted(by_kind.items())),
                  sum(op["dur"] for op in flash) - seconds, steps))
    return 100.0 * flops / run["peaks"]["flops_bf16"] / seconds
