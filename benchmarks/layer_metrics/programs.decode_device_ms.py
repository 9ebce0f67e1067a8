"""Mean device time of one jit__decode_fn execution."""
from benchmarks import layer_lib

UNIT = "ms"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return layer_lib.module_ms(trace, layer_lib.DECODE)
