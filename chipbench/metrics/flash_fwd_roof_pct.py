"""The flash FORWARD kernel's share of the compute roofline: 2/7 of
``arith.flash_flops_per_step`` (forward is 2 of the 7 causal-useful
matmuls) in the traced steps over the peak bf16 rate over the device
time of the kernel named ``flash_fwd`` (``pl.pallas_call(name=...)``)
on chip 0. None where the program's kernels carry no names."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(run):
    value, seconds = spans.roof_pct(run, ("flash_fwd",), 2.0 / 7.0)
    if value is not None:
        spans.say("flash_fwd_roof_pct: flash_fwd %.6f s of device time"
                  % seconds["flash_fwd"])
    return value
