"""Time to move the streams of the traced prompts' tokens through every sublayer's
hyper-connection (12 sublayers x 100,352 B a token) at peak HBM bytes/s, over the device
time under ``mhc`` in the prefill program.  Bound: memory."""
from benchmarks import hc_moe_mla_spans as spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.mhc_roofline(counters, spans.analysis(trace))
