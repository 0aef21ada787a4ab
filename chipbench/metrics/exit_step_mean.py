"""The mean expected exit step of a looped model over the whole run:
``train.counters["exit_step"]`` (the masked mean of ``sum_t t p_t``,
which the program sums on the device in every train step,
``layers.step_sum``) over ``["steps"]``. Between 1 and the visits: a
fresh Ouro reads 1.875 (every gate about a half: p is 0.5, 0.25, 0.125
and the remainder 0.125 of four visits); a gate that collapses to one
visit, the failure the objective's entropy term is there against,
reads 1 or the number of visits. None where either sum is missing or
no step was counted."""
UNIT, SOURCE = "visits", "program_counter"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    counters = run["train"].get("counters") or {}
    total, steps = counters.get("exit_step"), counters.get("steps")
    if not total or not steps or not steps[0]:
        return None
    return total[0] / steps[0]
