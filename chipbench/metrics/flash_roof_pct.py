"""The flash kernels' share of the compute roofline: the causal-useful
FLOPs of flash forward and backward in the traced steps
(``arith.flash_flops_per_step``, from shapes; the cell's chips share
them equally) over the peak bf16 rate, over the device time on chip 0
of the kernels NAMED ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``
and ``flash_bwd`` (``pl.pallas_call(name=...)``; those of them that
ran), through ``spans.roof_pct`` like its two siblings: a Pallas kernel
of another name (a grouped matmul) is not flash time. Compute bounds
these kernels: at T 2048 and head size 64 their FLOPs over the peak
rate take longer than their bytes over the peak bandwidth. None where
the program's kernels carry none of the names."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd")


def read(run):
    return spans.roof_pct(run, KERNELS, 1.0)[0]
