"""memory_stats()['peak_bytes_in_use'] on the fullest chip, in 1e9 bytes."""
from benchmarks import layer_lib

UNIT = "GB"
LAYER = "device"
SOURCE = "program_counter"


def read(counters, trace):
    return layer_lib.ratio(counters["device"].get("memory_peak_bytes") or 0, 1e9) or None
