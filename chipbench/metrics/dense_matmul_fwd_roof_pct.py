"""The dense matmuls' FORWARD share of the compute roofline: 2 MKN of
every ``mul`` / ``matmul`` row of the train step's table of the op
ledger (``paddle_tpu.trace.ops``) times the traced steps, over the peak
bf16 rate, over the device time of the ops scoped to those rows in the
forward (neither ``transpose(jvp(`` nor ``rematted_computation/`` in the
op's name; chip 0; ``chipbench/oplog.py``). ``dense_matmul_roof_pct``'s
log lines give it by family."""
from chipbench import oplog

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    return oplog.roof_pct(run, ("fwd",))[0]
