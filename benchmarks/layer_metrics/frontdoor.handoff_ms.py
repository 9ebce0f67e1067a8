"""Median over the traced wakes of: end of the last token-bearing ``server.write`` before the
next ``server.wake`` minus START of the engine thread's stream hand-off
(``engine.emit`` with ``streams=``) that posted the wake: how long a token the
engine has waits for the socket.  The wake is posted inside that span, so the
span's end can come after the write (PR 42)."""
from benchmarks import thread_spans

UNIT = "ms"
LAYER = "front door"
SOURCE = "program_span"


def read(counters, trace):
    return thread_spans.value(trace, "frontdoor.handoff_ms")
