"""Set-up seconds JAX spent tracing and lowering before the measured
window: the program's compile log (``jaxpr_trace_duration`` and
``jaxpr_to_mlir_module_duration`` of every jitted function; nested
phases count once). The log line splits them by function, so the
program's ``step`` and the reference's functions are told apart, and
counts the phases that ended inside the window (there should be none).
None where the program keeps no compile log."""
from chipbench import spans

UNIT, SOURCE = "s", "program_counter"
LAYER, MOVES = "train executor", "setup_s"
PHASES = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration")


def read(run):
    return spans.setup_seconds(run, "setup_trace_lower_s.train", PHASES)
