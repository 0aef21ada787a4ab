"""Wall time until the executor's ``run`` returns (the step is then in
flight), median over the window's steps."""
from chipbench import records

UNIT, SOURCE = "ms", "host_clock"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    return records.percentile(run["train"]["dispatch_ms"], 50)
