"""How evenly the router loads the experts held here: the rows the
fullest held expert took over the mean of the held experts, over the
whole run (``train.counters["expert_rows"]``, which the program sums on
the device in every train step). 1.0 is an even load; a grouped matmul's
time follows the sum, the slowest chip of an expert-parallel group the
maximum. None where the architecture counts no ``expert_rows``."""
UNIT, SOURCE = "ratio", "program_counter"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    rows = (run["train"].get("counters") or {}).get("expert_rows")
    cfg = run["config"]
    if not rows or "first_expert" not in cfg:
        return None
    held = rows[cfg["first_expert"]:cfg["first_expert"] + cfg["num_experts"]]
    if not sum(held):
        return None
    return max(held) * len(held) / sum(held)
