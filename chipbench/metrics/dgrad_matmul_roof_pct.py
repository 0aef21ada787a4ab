"""The dense matmuls' OPERAND gradients' share of the compute roofline:
as ``wgrad_matmul_roof_pct``, for the products under a ``mul`` /
``matmul`` row's backward that sum over the row's N columns
(``chipbench/kernels.py`` ``grad_kind``: where M = N the operand
gradient is the one that reads an operand of the weight's shape). Exact
FLOPs over the whole time of the kernels that hold them (chip 0), so
never over 100; what rides there is the backward of what fed the
product (a recomputed ``silu * up``, a norm's gradient). The log lines
are ``wgrad_matmul_roof_pct``'s, for this kind."""
from chipbench import kernels

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    return kernels.grad_roof_pct(run, "x", "dgrad_matmul_roof_pct")
