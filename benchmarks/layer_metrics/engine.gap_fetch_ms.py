"""Device idle time between step programs while the engine was in ``engine.fetch`` (the host arrays a step reads: its int32 tokens, 4 B a row of the bucket; logits only for a launch the numerics audit samples), per launch.
With the other ``gap_*`` metrics, the idle time under ``engine.wait`` and the
unattributed rest it sums to ``engine.host_ms_per_step`` of the same trace."""
from benchmarks import host_spans

UNIT = "ms"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    return host_spans.gap_ms(trace, "engine.fetch")
