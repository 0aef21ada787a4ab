"""The share of device time that belongs to no Program op and no
serving scope: ops whose ``op_name`` names none (copies and slices the
compiler put in, the RNG seed programs), over busy time (chip 0). The
log line checks the books: scoped plus unscoped is the sum of chip 0's
device-op times."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "device", "tokens_per_s"


def read(run):
    window = spans.of(run)
    if not window or not window["ops"]:
        return None
    unscoped = spans.device_time(window, scope=None)
    total = spans.ops_total(window)
    spans.say("unscoped_dev_share_pct: unscoped %.6f s + scoped %.6f s "
              "= %.6f s, the sum of chip 0's device-op times; busy "
              "(their union) %.6f s" % (
                  unscoped, total - unscoped, total,
                  run["trace"]["busy_s"]))
    return spans.busy_share_pct(run, unscoped)
