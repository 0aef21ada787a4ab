"""Gap between output tokens, token-weighted: the sum over the window's
requests of (retire - first token) over the sum of (tokens - 1). The
engine stamps no single token, so this is the steadiest gap statistic
its stamps allow."""
from chipbench import records

UNIT, SOURCE, LAYER, MOVES = "ms", "host_clock", None, None


def read(run):
    reqs = [r for r in records.done(run) if r["tokens"] > 1]
    gaps = sum(r["tokens"] - 1 for r in reqs)
    if not gaps:
        return None
    return 1e3 * sum(r["retire"] - r["first"] for r in reqs) / gaps
