"""Time to read the global layer's pages of the cache tokens the traced decode launches
had to read (4,096 B a token) at peak HBM bytes/s, over the device time under
``attn_global`` in the decode program.  Bound: memory."""
from benchmarks import window_moe_spans as spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.global_decode_roofline(counters, spans.analysis(trace))
