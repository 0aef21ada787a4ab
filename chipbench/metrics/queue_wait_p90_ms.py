"""``Request.queue_wait`` (submit to slot admission), 90th percentile
over the window's requests."""
from chipbench import records

UNIT, SOURCE = "ms", "program_span"
LAYER, MOVES = "serving engine", "ttft_p90_ms"


def read(run):
    waits = [1e3 * (r["admit"] - r["submit"])
             for r in records.window(run)
             if r.get("admit") is not None]
    return records.percentile(waits, 90)
