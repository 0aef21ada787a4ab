"""Set-up seconds JAX spent in the backend before the measured window:
the program's compile log (``backend_compile_duration``, which is the
compile or, on a hit, the persistent cache's load, and
``cache_retrieval_time_sec``, which lies inside it: nested phases count
once). The log line splits them by function, gives the persistent
cache's hits and misses, and counts the phases that ended inside the
window (there should be none). None where the program keeps no compile
log."""
from chipbench import spans

UNIT, SOURCE = "s", "program_counter"
LAYER, MOVES = "train executor", "setup_s"
PHASES = ("backend_compile_duration", "cache_retrieval_time_sec")


def read(run):
    total = spans.setup_seconds(run, "setup_compile_s.train", PHASES)
    if total is not None:
        log = spans.of(run)["compiles"]
        spans.say("setup_compile_s.train: persistent cache hits %d, "
                  "misses %d, retrieval %.3f s in the whole run" % (
                      sum(r["what"] == "cache_hits" for r in log),
                      sum(r["what"] == "cache_misses" for r in log),
                      sum(r["seconds"] for r in log if r["what"]
                          == "cache_retrieval_time_sec")))
    return total
