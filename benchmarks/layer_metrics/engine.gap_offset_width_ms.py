"""How well the host plane is aligned to the device plane: the width of the
interval causality leaves for the offset between them, over every traced launch
(a program starts after its ``engine.dispatch`` and its ``DoEnqueueProgram`` began;
the ``engine.device_wait`` of the same ``launch`` number and its ``CompleteCallbacks``
end after it ended).  A phase shorter than this is not resolved.  ``None``, with
the reason on standard error, where a trace whose launches are numbered leaves it
negative or over 2 ms: the pairing is then wrong and so is every ``engine.gap_*``."""
from benchmarks import host_spans

UNIT = "ms"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    a = host_spans.analysis(trace)
    return None if a is None else host_spans.offset_width_ms(a)
