"""The dense matmuls' BACKWARD share of the compute roofline: 2 MKN for
each gradient the step takes (``grads``) of every ``mul`` / ``matmul``
row of the train step's table of the op ledger
(``paddle_tpu.trace.ops``) times the traced steps, over the peak bf16
rate, over the device time of the ops scoped to those rows in the
backward (``transpose(jvp(`` in the op's name and no
``rematted_computation/``; chip 0; ``chipbench/oplog.py``). Adam's
update rides in the weight gradient's fusion and the cross-entropy's
gradient in the head's, so this reads low, and lower than the forward.
``dense_matmul_roof_pct``'s log lines give it by family."""
from chipbench import oplog

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    return oplog.roof_pct(run, ("bwd",))[0]
