"""Device time under ``moe_dispatch`` + ``moe_combine`` over all time under ``mlp``: what
the seven routed pairs in eight that are another chip's still cost here (the sort over
all pairs, the sum back per token)."""
from benchmarks import window_moe_spans as spans

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.moe_absent_pairs_share(spans.analysis(trace))
