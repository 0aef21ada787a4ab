"""Model FLOP/s utilization: tokens per second x the FLOPs a token
requires (``arith.train_flops_per_token``: forward + backward, no
recompute) over chips x peak. In a traced run the traced stretch, with
the profiler's start and stop, is left out of the rate."""
from chipbench import arith

UNIT, SOURCE = "%", "host_clock"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    t = run["train"]
    rate = (t["steps"] - t["traced_steps"]) * t["tokens_per_step"] \
        / (t["window_s"] - t["traced_s"])
    per_token = arith.train_flops_per_token(run["config"], t["seq_len"])
    return 100.0 * rate * per_token / (
        run["chips"] * run["peaks"]["flops_bf16"])
