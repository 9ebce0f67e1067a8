"""Step programs traced and compiled inside the window (the engine's in-trace
counters). Must read 0."""

UNIT = "count"
LAYER = "programs"
SOURCE = "program_counter"


def read(counters, trace):
    return counters["window"]["compiles"]
