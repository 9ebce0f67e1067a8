"""Device idle time between programs while the engine was in ``engine.dispatch`` (the step call until the jit call returns), per step launch.
The idle ends of ``engine.device_wait`` (the program not yet started, its tokens
on their way back) are the same round trip and are counted here.  Since the
serving loop runs ahead this is the round trip of the launches that did NOT
(``ahead=0``: the step waited for them); idle time under a dispatch with
``ahead=1`` and the wait for its program -- a launch that ran ahead and still came
late -- is ``engine.dispatch.ahead`` / ``engine.device_wait.ahead`` in
``host_spans.py``'s table and in no ``gap_*`` metric (``engine.idle_settled_share``
says how the idle seconds split).  With that, the other ``gap_*`` metrics, the
idle time under ``engine.wait`` and the unattributed rest it sums to
``engine.host_ms_per_step`` of the same trace."""
from benchmarks import host_spans

UNIT = "ms"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    a, b = (host_spans.gap_ms(trace, p)
            for p in ("engine.dispatch", "engine.device_wait"))
    return None if a is None else a + b
