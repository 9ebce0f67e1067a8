"""Mean of arrival at the engine to first prefill launch, over requests
admitted in the window (serving_queue_wait_seconds)."""
from benchmarks import layer_lib

UNIT = "ms"
LAYER = "scheduler"
SOURCE = "program_counter"


def read(counters, trace):
    return layer_lib.ratio(counters["window"]["queue_wait_s"], counters["window"]["queue_wait_n"], 1e3)
