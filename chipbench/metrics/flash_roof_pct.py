"""The flash kernels' share of the compute roofline: the causal-useful
FLOPs of flash forward and backward in the traced steps
(``arith.flash_flops_per_step``, from shapes; the cell's chips share
them equally) over the peak bf16 rate, over the device time of the
train step's Pallas kernels (the ``tpu_custom_call`` ops of the jitted
program that holds them: flash forward, dq and dk/dv) on chip 0. Compute bounds these kernels: at
T 2048 and head size 64 their FLOPs over the peak rate take longer than
their bytes over the peak bandwidth."""
from chipbench import arith

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(run):
    trace, t = run.get("trace"), run["train"]
    if not trace or not trace["kernel_s"]:
        return None
    program = max(trace["kernel_s"], key=trace["kernel_s"].get)
    kernel_s = trace["kernel_s"][program]
    steps = len(trace["modules"][program])
    flops = steps * arith.flash_flops_per_step(
        run["config"], t["batch"], t["seq_len"]) / run["chips"]
    return 100.0 * flops / run["peaks"]["flops_bf16"] / kernel_s
