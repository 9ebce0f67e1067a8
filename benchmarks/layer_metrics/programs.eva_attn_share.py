"""Share of device busy time under ``eva_attn`` (ring and summary reads, the one softmax,
a prompt's windowed attention) and ``eva_pool`` (summarising chunks), in every program."""
from benchmarks import eva_spans as spans

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.eva_attn_share(trace, spans.analysis(trace))
