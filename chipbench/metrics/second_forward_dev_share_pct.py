"""A recompute region's second forward as a share of device time: the
device time of the step's ops whose ``op_name`` carries
``rematted_computation/`` (what the backward of a ``layers.recompute``
region runs again; ``chipbench/oplog.py`` ``pass_of``), scoped or not,
over busy time (chip 0). None where the train step's table of the op
ledger (``paddle_tpu.trace.ops``) has no row with a ``region``: the
program has no region, or keeps no ledger.

The log lines give ms a step by op type inside regions, largest first,
each beside how many of its rows the regions' plan kept (``kept``): a
type whose every row is kept runs no second forward, and what a next
recompute candidate would save is the line of its type."""
from chipbench import oplog, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    window = oplog.of(run)
    if window is None:
        return None
    inside = [r for r in window["rows"].values() if r["region"] is not None]
    if not inside:
        return None
    steps = window["steps"]
    seconds = {}
    for op in window["ops"]:
        if op["pass"] == "second":
            what = spans.scope_type(op["scope"]) or "(no scope)"
            seconds[what] = seconds.get(what, 0.0) + op["dur"]
    rows, kept = {}, {}
    for r in inside:
        rows[r["type"]] = rows.get(r["type"], 0) + 1
        kept[r["type"]] = kept.get(r["type"], 0) + (r["kept"] is not None)
    total = sum(seconds.values())
    spans.say("second_forward_dev_share_pct: %.3f ms a step in %d "
              "regions of %d ops" % (
                  1e3 * total / steps,
                  len({r["region"] for r in inside}), len(inside)))
    for what in sorted((w for w in set(rows) | set(seconds)
                        if seconds.get(w) or kept.get(w)),
                       key=lambda w: (-seconds.get(w, 0.0), w)):
        spans.say("second_forward_dev_share_pct: %s %.3f ms a step, %s" % (
            what, 1e3 * seconds.get(what, 0.0) / steps,
            "%d of %d kept" % (kept[what], rows[what]) if kept.get(what)
            else "none kept" if what in rows else "no row in a region"))
    return spans.busy_share_pct(run, total)
