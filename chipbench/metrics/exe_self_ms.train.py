"""The executor's own host time in a step: ``exe.step`` less the
``exe.dispatch`` inside it (the call of the jitted entry), median over
the traced steps. It is what ``step_host_ms.train`` holds besides the
runtime's dispatch, taken under the profiler, which slows the host.
None where the program opens no spans."""
from chipbench import spans

UNIT, SOURCE = "ms", "program_span"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    return spans.self_ms(run, "exe.step", ("exe.dispatch",))
