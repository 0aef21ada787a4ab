"""Median device duration of one run of the decode-step program, in the
traced window."""
from chipbench import records

UNIT, SOURCE = "ms", "device_trace"
LAYER, MOVES = "served model", "itl_mean_ms"
PROGRAM = "_step_impl"


def read(run):
    return records.percentile(
        [1e3 * d for d in records.module_runs(run, PROGRAM)], 50)
