"""The matmuls' share of the compute roofline: the FLOPs a token
requires outside attention (``arith.train_flops_per_token`` at sequence
length 0: q/k/v/o, FFN and head, forward and backward) times the traced
tokens over the peak bf16 rate, over the device time of the ops scoped
to a Program ``mul`` op in either direction (chip 0). XLA fuses
elementwise neighbours into a matmul's fusion (bias, the optimizer's
update of the weight, the cross-entropy's gradient into the head's):
their time is in the divisor, so this reads low rather than high."""
from chipbench import arith, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, steps = spans.step_program(window)
    seconds = spans.device_time(window, program, scope_type="mul")
    if not seconds:
        return None
    t = run["train"]
    flops = steps * t["tokens_per_step"] * arith.train_flops_per_token(
        run["config"], 0) / run["chips"]
    spans.say("matmul_roof_pct: mul forward %.6f s, backward %.6f s of "
              "device time in %d steps" % (
                  spans.device_time(window, program, scope_type="mul",
                                    direction="fwd"),
                  spans.device_time(window, program, scope_type="mul",
                                    direction="bwd"), steps))
    return 100.0 * flops / run["peaks"]["flops_bf16"] / seconds
