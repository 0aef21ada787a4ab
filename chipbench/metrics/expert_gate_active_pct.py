"""How much of the experts' hidden width a ReLU gate leaves on: of the
hidden units of every (row, held expert) pair, the share with ``h2
W_gate > 0``, over the whole run (``train.counters``:
``expert_gate_active`` over ``expert_gate_units``, which the expert
layer sums on the device in every train step). The rest are exact
zeros: rows of the down projection's operand, and of its backward's,
that no product needs. At initialisation a gate is on for half; what a
trained model's sparsity would allow is what a later change to the down
projection reads here. None where the program counts no gate (another
gate than ReLU, or a program from before PR 46)."""
UNIT, SOURCE = "%", "program_counter"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    counters = run["train"].get("counters") or {}
    active = counters.get("expert_gate_active")
    units = counters.get("expert_gate_units")
    if not active or not units or not units[0]:
        return None
    return 100.0 * active[0] / units[0]
