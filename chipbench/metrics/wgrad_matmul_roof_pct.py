"""The dense matmuls' WEIGHT gradients' share of the compute roofline,
counted by the program's two ledgers: the FLOPs of every product the
kernel ledger (``paddle_tpu.trace.kernels``, ``dots``) finds in a kernel
of the compiled step under a ``mul`` / ``matmul`` row's backward and
that sums over the row's M rows (``chipbench/kernels.py``
``grad_kind``; each checked against the op ledger's 2 MKN, a mismatch
said and left out), times the runs of the kernels that hold them, over
the peak bf16 rate, over those kernels' WHOLE device time (chip 0). The
FLOPs are exact and the time is the kernel's own with everything XLA
fused into it (Adam's update of the weight, an activation made again
for the product's operand), so this cannot read over 100 and reads as
low as the riders make the kernel. With ``dgrad_matmul_roof_pct`` it
splits ``dense_matmul_bwd_roof_pct``; a kernel that holds gradients of
both kinds, or one whose kind M = N hides, is in neither and its
seconds are said.

The log lines give the gradients by weight FAMILY (as
``dense_matmul_roof_pct`` groups them), furthest from the peak's time
first: ms a step, the share of the peak, the kernel's declared bytes
over the product's own three arrays (what the riders move), the riders
by op type with their instructions a kernel, and the measured
nanoseconds for each cycle XLA estimated; then the books, with the
op ledger's backward FLOPs beside the kernel ledger's."""
from chipbench import kernels

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    return kernels.grad_roof_pct(run, "w", "wgrad_matmul_roof_pct")
