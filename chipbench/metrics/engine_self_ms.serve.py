"""The serving engine's own host time in an iteration: ``engine.step``
less the ``engine.dispatch`` and ``engine.fetch`` inside it (the call
of the decode program and the wait for its tokens), median over the
traced iterations that decoded. None where the program opens no
spans."""
from chipbench import spans

UNIT, SOURCE = "ms", "program_span"
LAYER, MOVES = "serving engine", "itl_mean_ms"


def read(run):
    return spans.self_ms(run, "engine.step",
                         ("engine.dispatch", "engine.fetch"))
