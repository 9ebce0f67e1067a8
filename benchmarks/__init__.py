"""The repo's benchmark: the yardstick later PRs are measured with.

Everything here is the benchmark's own: traffic generation, the reduction
from traces and counters to metrics, the table of peaks, the functions
that count a kernel's operations and bytes, each configuration's plain
reference and the comparison that decides ``correct``.  From the program
it takes only the system under test and its spans, counters and kernel
names.  ``BENCHMARK.json`` at the repo root names the cells; whatever
belongs to one configuration, traffic mix or metric is a file of its own
found by that name (see ``harness.py``).
"""
