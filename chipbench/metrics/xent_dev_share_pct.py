"""The loss's share of device time: ops scoped to the Program's
``softmax_with_cross_entropy`` op and to the ops of the differentiated
forward that come after it (the masked mean: ``elementwise_*``,
``reduce_sum``, ``scale``), forward and backward, over busy time
(chip 0). What XLA fused into the head matmul's fusions is not here."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "train executor", "tokens_per_s"
LOSS_OP = "softmax_with_cross_entropy"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    seq = lambda op: int(op["scope"].rsplit(".", 1)[1])
    mine = [op for op in window["ops"] if op["program"] == program
            and op["direction"] and op["scope"]]
    first = [seq(op) for op in mine
             if spans.scope_type(op["scope"]) == LOSS_OP]
    if not first:
        return None
    return spans.busy_share_pct(run, sum(
        op["dur"] for op in mine if seq(op) >= min(first)))
