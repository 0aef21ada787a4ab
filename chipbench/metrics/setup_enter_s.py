"""Process start (``run.py``'s ``T_START``) to the driver's ``run``:
the imports, the device check (the chip's start) and the cell's files,
all of it before any work of the cell's. It moves nothing: it is there
so that the ledger can say of a ``setup_s`` that jumped whether the
cell's work did it or the machine's start (``setup_s`` less this is
the driver's own set-up; the driver's ``setup_phases`` split that
further, in its log line)."""
UNIT, SOURCE = "s", "host_clock"
LAYER, MOVES = "entry points", "setup_s"


def read(run):
    return run.get("setup_phases", {}).get("entered")
