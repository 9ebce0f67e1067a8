"""Time to read the cache the traced decode steps had to read at peak HBM
bytes/s, over the device time of the decode program's attention kernel.
Bound: memory."""
from benchmarks import layer_lib

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return layer_lib.paged_decode_roofline(counters, trace)
