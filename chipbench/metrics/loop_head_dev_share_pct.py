"""A head and a loss behind EVERY visit of a looped stack as a share of
device time: the device time of the step's ops whose row of the
program's op ledger (``paddle_tpu.trace.ops``, joined by
``chipbench/oplog.py``) says ``module`` ``loop_head`` (what
``models/looped_lm.py`` builds inside ``layers.module("loop_head")``:
each visit's product with the one head weight, its cross-entropy and
the reshapes round them), forward, a region's second forward and
backward alike, over busy time (chip 0). A plain model of the same
size pays one head a step; this reads what ``total_ut_steps`` of them
cost. The ops have a plain head's types, so no scope's type tells them
from the stack's; the row's ``module`` does.

The log line gives ms a step by pass. None where the ledger's rows
state no ``module`` (a tree from before PR 55), where no row is the
module's (a program with no loop: the parent of PR 59), or where there
is no ledger."""
from chipbench import oplog, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
MODULE = "loop_head"


def module_share_pct(run, module, metric):
    """The share of busy time in the ops of ``module``, with the log
    line under ``metric``'s name (``exit_dev_share_pct`` reads the
    module ``exit`` with it)."""
    window = oplog.of(run)
    if window is None:
        return None
    mine = [op for op in window["ops"] if op["row"]
            and op["row"].get("module") == module]
    if not mine:
        return None
    passes = dict.fromkeys(oplog.PASSES, 0.0)
    for op in mine:
        passes[op["pass"]] += op["dur"]
    total = sum(passes.values())
    spans.say("%s: %.3f ms a step in the ops of module %s: %s" % (
        metric, 1e3 * total / window["steps"], module, ", ".join(
            "%s %.3f" % (name, 1e3 * s / window["steps"])
            for name, s in passes.items())))
    return spans.busy_share_pct(run, total)


def read(run):
    return module_share_pct(run, MODULE, "loop_head_dev_share_pct")
