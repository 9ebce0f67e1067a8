"""Time to read the latent cache the traced decode steps had to read (1,152 B a
token a layer) at peak HBM bytes/s, over the device time under ``mla_decode_core`` in
the decode program.  Bound: memory."""
from benchmarks import moe_mla_spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return moe_mla_spans.mla_decode_roofline(counters,
                                             moe_mla_spans.analysis(trace))
