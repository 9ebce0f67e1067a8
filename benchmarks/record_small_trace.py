#!/usr/bin/env python3
"""Record the small device trace that ``trace_reduce``'s test reads.

    chiprun -- python benchmarks/record_small_trace.py

Two tiny jitted programs with the names the serving engine's programs have
in a trace (``_decode_fn``, ``_prefill_fn``), a few executions each with
host sleeps between them so that the device idles, under the JAX profiler.
Writes ``chiprun_out/small_trace.xplane.pb`` and, beside it, what
``trace_reduce.reduce`` made of it (``small_trace.expected.json``); the
builder copies both to ``benchmarks/data/``.  Needs the chip: a CPU trace
has no device plane.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks import trace_reduce

    if jax.default_backend() != "tpu":
        print("record_small_trace: needs a TPU", file=sys.stderr)
        return 2

    def _decode_fn(x, w):
        return jnp.sort(jnp.tanh(x @ w), axis=-1)

    def _prefill_fn(x, w):
        return (x @ w) @ w.T

    dec, pre = jax.jit(_decode_fn), jax.jit(_prefill_fn)
    x = jnp.ones((256, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16)
    dec(x, w).block_until_ready()
    pre(x, w).block_until_ready()
    log_dir = os.path.join(os.path.dirname(HERE), ".bench_trace", "small")
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir)
    for i in range(6):
        (pre if i % 3 == 2 else dec)(x, w).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(log_dir)
    red = trace_reduce.reduce(trace_reduce.load(path))
    out = os.path.join(os.path.dirname(HERE), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "small_trace.xplane.pb"))
    want = {k: red[k] for k in ("chips", "window_s", "busy_s", "gap_s",
                                "launches", "idle_share")}
    want["module_counts"] = {k: v["count"] for k, v in red["modules"].items()}
    want["ops"] = red["ops"]
    want["device_kind"] = jax.devices()[0].device_kind
    with open(os.path.join(out, "small_trace.expected.json"), "w") as f:
        json.dump(want, f, indent=1)
    print(json.dumps(want), os.path.getsize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main())
