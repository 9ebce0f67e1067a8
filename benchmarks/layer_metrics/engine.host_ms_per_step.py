"""Idle gap on the device between consecutive step programs, per launch: what
the host loop costs a step."""
from benchmarks import layer_lib

UNIT = "ms"
LAYER = "engine host loop"
SOURCE = "device_trace"


def read(counters, trace):
    return layer_lib.host_ms_per_step(trace)
