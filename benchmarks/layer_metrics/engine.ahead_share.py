"""Of the traced decode launches, the share whose ``engine.dispatch`` carries
``ahead=1``: it went out before the tokens of the launch before it were read.
``None`` for a program without the spans of ``tracer.THREAD_SPANS``."""
from benchmarks import thread_spans

UNIT = "%"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    return thread_spans.value(trace, "engine.ahead_share")
