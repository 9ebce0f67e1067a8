"""Largest share of the block pool held by sequences at any launch of the
window."""

UNIT = "%"
LAYER = "cache"
SOURCE = "program_counter"


def read(counters, trace):
    return 100.0 * counters["window"]["pool_peak_share"]
