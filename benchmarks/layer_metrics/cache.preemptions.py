"""Requests preempted for want of blocks in the window
(serving_preemptions_total)."""

UNIT = "count"
LAYER = "cache"
SOURCE = "program_counter"


def read(counters, trace):
    return counters["window"]["preemptions"]
