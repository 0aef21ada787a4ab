"""The multi-token-prediction module as a share of device time: the
device time of the step's ops whose row of the program's op ledger
(``paddle_tpu.trace.ops``, joined by ``chipbench/oplog.py``) says
``module`` ``mtp`` (the architecture's ``MODULE``: what the model built
inside ``layers.module``), forward, a region's second forward and
backward alike, over busy time (chip 0). The module's ops have the main
stack's types (one more block, the head and the loss a second time), so
no scope's type tells them apart; the row's ``module`` does. The ragged
matmuls of the module's held experts carry no scope (XLA strips it) and
are not in this time: 2% of the step's FLOPs in all six blocks.

The log line gives ms a step by part: the block (the rows inside the
module's recompute region), ``eh_proj``, the head (the product with the
main model's head weight), the loss (cross-entropy and what weighs it)
and the rest (the table looked up again, three norms, the shifts). None
where the ledger's rows state no ``module`` (a tree from before PR 55),
where no row is the module's, or where there is no ledger."""
from chipbench import cells, oplog, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
LOSS = ("softmax_with_cross_entropy", "elementwise_mul", "elementwise_div",
        "reduce_sum", "step_sum", "scale", "elementwise_add")


def part_of(row):
    if row["region"] is not None:
        return "block"
    if row["type"] == "mul":
        return "eh_proj" if any("eh_proj" in w for w in row["weights"]) \
            else "head"
    return "loss" if row["type"] in LOSS else "rest"


def read(run):
    window = oplog.of(run)
    if window is None:
        return None
    module = getattr(cells.load_arch(run["config"]["arch"]), "MODULE", None)
    mine = [op for op in window["ops"] if op["row"]
            and op["row"].get("module") == module]
    if module is None or not mine:
        return None
    parts = dict.fromkeys(("block", "eh_proj", "head", "loss", "rest"), 0.0)
    for op in mine:
        parts[part_of(op["row"])] += op["dur"]
    total = sum(parts.values())
    spans.say("mtp_dev_share_pct: %.3f ms a step in the module's ops: %s" % (
        1e3 * total / window["steps"], ", ".join(
            "%s %.3f" % (part, 1e3 * s / window["steps"])
            for part, s in parts.items())))
    return spans.busy_share_pct(run, total)
