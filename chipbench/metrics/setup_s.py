"""Process start to the first measured step, or to the start of the
traffic's pre-roll: imports, weights, compiles or cache loads, warm-up
and the correctness sample."""
UNIT, SOURCE, LAYER, MOVES = "s", "host_clock", None, None


def read(run):
    return run["setup_s"]
