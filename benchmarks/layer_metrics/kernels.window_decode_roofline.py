"""Time to read, in the three window layers, the ring entries the traced decode launches'
rows see (``min(length, 4096)`` a row, 4,096 B each a layer) at peak HBM bytes/s, over
the device time under ``attn_window`` in the decode program.  Bound: memory."""
from benchmarks import window_moe_spans as spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.window_decode_roofline(counters, spans.analysis(trace))
