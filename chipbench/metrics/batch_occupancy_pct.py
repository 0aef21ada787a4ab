"""Share of decode-step slots that held a live request, from the
engine's counters over the window:
``active_slot_steps / (decode_steps x slots)``."""
UNIT, SOURCE = "%", "program_counter"
LAYER, MOVES = "serving engine", "itl_mean_ms"


def read(run):
    s = run["engine"]["stats"]
    if not s["decode_steps"]:
        return None
    return 100.0 * s["active_slot_steps"] / (
        s["decode_steps"] * run["engine"]["slots"])
