"""Padded minus scheduled tokens and rows over padded, all step programs of the
window (StepProfiler)."""
from benchmarks import layer_lib

UNIT = "%"
LAYER = "scheduler"
SOURCE = "program_counter"


def read(counters, trace):
    return layer_lib.padding_share(counters)
