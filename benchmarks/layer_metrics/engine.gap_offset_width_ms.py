"""How well the host plane is aligned to the device plane: the width of the
interval causality leaves for the offset between them, over every traced launch
(a program starts after its ``engine.dispatch`` began; ``engine.device_wait`` ends
after its program ended).  A phase shorter than this is not resolved."""
from benchmarks import host_spans

UNIT = "ms"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    a = host_spans.analysis(trace)
    return None if a is None else 1e3 * a["offset_width_s"]
