"""How late chipbench's generator submitted: actual submit against the
due time, 99th percentile. A starved generator must not read as a fast
server."""
from chipbench import records

UNIT, SOURCE = "ms", "host_clock"
LAYER, MOVES = "entry points", "ttft_p90_ms"


def read(run):
    late = [1e3 * (r["submit"] - r["due"])
            for r in records.window(run) if "submit" in r]
    return records.percentile(late, 99)
