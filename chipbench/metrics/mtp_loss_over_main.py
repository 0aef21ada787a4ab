"""The multi-token-prediction module's loss over the main model's, over
the whole run: ``train.counters["mtp_loss"]`` over ``["main_loss"]``,
the two terms of the cost before the module's is weighed, which the
program sums on the device in every train step (``layers.step_sum``).
A fresh model reads 1.0 (both are about ln V); a module that learns
slower than the stack it sits on reads over 1, and one that fell out of
the cost reads nothing: None where either sum is missing or zero."""
UNIT, SOURCE = "ratio", "program_counter"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    counters = run["train"].get("counters") or {}
    main, ahead = counters.get("main_loss"), counters.get("mtp_loss")
    if not main or not ahead or not main[0] or not ahead[0]:
        return None
    return ahead[0] / main[0]
