"""Training tokens per second over all chips of the cell: every step
dispatched in the window, over the time to the last loss's
``block_until_ready``."""
UNIT, SOURCE, LAYER, MOVES = "tokens/s", "host_clock", None, None


def read(run):
    t = run["train"]
    return t["steps"] * t["tokens_per_step"] / t["window_s"]
