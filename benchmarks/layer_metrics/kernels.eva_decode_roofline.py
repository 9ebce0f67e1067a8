"""Time to read, in the eight layers, the ring entries and summary rows the traced decode
launches' rows see (``eva_ring_tokens + eva_summary_rows`` on ``engine.build``, 16,384 B
each a layer) at peak HBM bytes/s, over the device time under ``eva_attn`` in the decode
program.  Bound: memory; the count is the same whatever implements the step."""
from benchmarks import eva_spans as spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.eva_decode_roofline(counters, spans.analysis(trace))
