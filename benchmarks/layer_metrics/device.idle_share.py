"""1 minus the union of device operation intervals over the traced window."""
from benchmarks import layer_lib

UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"


def read(counters, trace):
    return layer_lib.idle_share(trace)
