"""Share of device busy time under ``moe_router``, ``moe_dispatch`` and ``moe_combine``
in a cell whose launches are prompts of thousands of tokens: what routing costs beside
the experts' matmuls there."""
from benchmarks import hc_moe_mla_spans as spans

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.moe_prefill_overhead_share(trace, spans.analysis(trace))
