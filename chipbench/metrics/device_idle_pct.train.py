"""1 - union of device-op intervals over the traced window, mean over
the cell's chips."""
from chipbench import records

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "device", "tokens_per_s"
read = records.idle_pct
