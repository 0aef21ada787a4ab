"""The optimizer's share of device time: ops scoped to the Program's
``adam`` ops over busy time (chip 0). An update that XLA fused into the
fusion of the matmul that makes its gradient is not here: that fusion
carries the matmul's name."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    seconds = spans.device_time(window, program, scope_type="adam")
    return spans.busy_share_pct(run, seconds) if seconds else None
