"""Time to read and write once the recurrent state of every real row the traced decode
steps advanced (358,400 B a row a mixer layer, each way) at peak HBM bytes/s, over the
device time under ``ssm_step`` in the decode program.  Bound: memory."""
from benchmarks import ssm_spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return ssm_spans.ssm_decode_roofline(counters, ssm_spans.analysis(trace))
