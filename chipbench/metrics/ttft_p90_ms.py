"""The tail of time to first token, from when each request was DUE:
90th percentile over the requests due in the window. It wants some
hundred requests in a window (ten samples beyond it)."""
from chipbench import records

UNIT, SOURCE, LAYER, MOVES = "ms", "host_clock", None, None


def read(run):
    return records.percentile(records.ttft_ms(run), 90)
