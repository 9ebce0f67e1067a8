"""Device idle time between step programs while the engine was in ``engine.dispatch`` (the step call until the jit call returns), per launch.
With the other ``gap_*`` metrics, the idle time under ``engine.wait`` and the
unattributed rest it sums to ``engine.host_ms_per_step`` of the same trace.
The idle ends of ``engine.device_wait`` (the program not yet started, its tokens
on their way back) are the same round trip and are counted here."""
from benchmarks import host_spans

UNIT = "ms"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    a, b = (host_spans.gap_ms(trace, p)
            for p in ("engine.dispatch", "engine.device_wait"))
    return None if a is None else a + b
