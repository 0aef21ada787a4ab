"""The flash BACKWARD kernels' share of the compute roofline: 5/7 of
``arith.flash_flops_per_step`` (s, dp, dv, dk, dq) in the traced steps
over the peak bf16 rate over the device time of the kernels named
``flash_bwd_dq`` and ``flash_bwd_dkv`` on chip 0; the log line gives
each kernel's time. None where the program's kernels carry no names."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
KERNELS = ("flash_bwd_dq", "flash_bwd_dkv")


def read(run):
    value, seconds = spans.roof_pct(run, KERNELS, 5.0 / 7.0)
    if value is not None:
        spans.say("flash_bwd_roof_pct: " + ", ".join(
            "%s %.6f s" % (k, seconds[k]) for k in KERNELS)
            + " of device time")
    return value
