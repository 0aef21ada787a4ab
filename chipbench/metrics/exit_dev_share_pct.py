"""A looped model's exit glue as a share of device time: the device
time of the step's ops whose row of the op ledger says ``module``
``exit`` (what ``models/looped_lm.py`` builds inside
``layers.module("exit")``: each visit's float32 gate, the exit
distribution in log space, the expected loss, the entropy and the
counters), forward, second forward and backward alike, over busy time
(chip 0). All of it is HBM-bound elementwise work on ``[R, B, T]``
float32 values and a ``[d, 1]`` product a visit: a fraction of a
percent where XLA fuses it, and what a change to the objective (a
second training stage, another prior) would move. None as
``loop_head_dev_share_pct`` is."""
import os

from chipbench import cells

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
MODULE = "exit"


def read(run):
    head = cells.load_metric("loop_head_dev_share_pct",
                             os.path.dirname(os.path.dirname(__file__)))
    return head.module_share_pct(run, MODULE, "exit_dev_share_pct")
