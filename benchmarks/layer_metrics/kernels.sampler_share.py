"""Share of device busy time in the sampler's full-vocabulary sort. A lower
bound of the sampler: its other fusions carry no name yet."""
from benchmarks import layer_lib

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return layer_lib.op_share(trace, "sort")
