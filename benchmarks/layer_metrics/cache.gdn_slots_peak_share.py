"""The largest ``state_slots_held`` of the traced launches' ``engine.build`` phases over
the slots there are (``max_num_seqs``), in the delta-rule cell: how full the per-sequence
state is."""
from benchmarks import gated_delta_spans as gdn

UNIT = "%"
LAYER = "cache"
SOURCE = "program_span"


def read(counters, trace):
    return gdn.slots_peak_share(counters, gdn.analysis(trace))
