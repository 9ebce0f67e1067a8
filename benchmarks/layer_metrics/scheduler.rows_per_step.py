"""Mean real rows of a decode launch in the window."""
from benchmarks import layer_lib

UNIT = "rows"
LAYER = "scheduler"
SOURCE = "program_counter"


def read(counters, trace):
    return layer_lib.rows_per_step(counters)
