"""The share of device time spent moving the KV pool: ops scoped
``kv.read`` (the slice or gather from the pool) and ``kv.write`` (the
scatter into it) in the serving programs, over busy time (chip 0).
The copies XLA puts round an update that is not in place carry no
scope: the log line gives their time beside these. None where the
program carries no such scope."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "served model", "itl_mean_ms"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    seconds = {s: spans.device_time(window, scope=s)
               for s in ("kv.read", "kv.write")}
    if not any(seconds.values()):
        return None
    spans.say("pool_move_dev_share_pct: kv.read %.6f s, kv.write %.6f s "
              "of device time; copies with no scope (not counted: the "
              "compiler's, of the pool round its update among them) "
              "%.6f s" % (seconds["kv.read"], seconds["kv.write"],
                          spans.device_time(window, kind="copy",
                                            scope=None)))
    return spans.busy_share_pct(run, sum(seconds.values()))
