"""The most ring entries the rows of one traced decode launch held valid (``window_tokens``
on ``engine.build``) over the entries of all rings (``max_num_seqs`` x window): how full
the window layers' per-sequence memory is."""
from benchmarks import window_moe_spans as spans

UNIT = "%"
LAYER = "cache"
SOURCE = "program_span"


def read(counters, trace):
    return spans.window_ring_peak_share(counters, spans.analysis(trace))
