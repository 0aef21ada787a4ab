"""Device time of the prefill-chunk program over device busy time, in
the traced window."""
from chipbench import records

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "served model", "ttft_p90_ms"
PROGRAM = "_prefill_impl"


def read(run):
    runs = records.module_runs(run, PROGRAM)
    if not runs:
        return None
    return 100.0 * sum(runs) / run["trace"]["busy_s"]
