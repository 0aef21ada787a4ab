"""The last visit's loss over the first visit's, over the whole run:
``train.counters["visit_loss"]`` holds each visit's masked mean
cross-entropy summed on the device over every train step
(``layers.step_sum``), visit by visit. A fresh model reads 1.0 (every
visit's is about ln V); a loop whose later visits refine the stream
reads under 1, and one whose visits past the first learn nothing reads
1 still. None where the sums are missing, fewer than two, or the first
is zero."""
UNIT, SOURCE = "ratio", "program_counter"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    visits = (run["train"].get("counters") or {}).get("visit_loss")
    if not visits or len(visits) < 2 or not visits[0]:
        return None
    return visits[-1] / visits[0]
