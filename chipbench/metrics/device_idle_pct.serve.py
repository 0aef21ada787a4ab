"""1 - union of device-op intervals over the traced window, mean over
the cell's chips. The served configuration has 8 of 24 layers, so the
host's share of a step is larger here than in a deployment."""
from chipbench import records

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "device", "itl_mean_ms"
read = records.idle_pct
