#!/usr/bin/env python3
"""Record the small trace that ``host_spans``'s test reads: host plane with
the engine's phases, device plane with its scopes.

    chiprun -- python benchmarks/record_phase_trace.py

A two-layer model of TPU-friendly widths served by the program's real
``EngineCore`` for a few steps under the JAX profiler: so the phases are
the ones ``SpanTracer.phase`` writes, the scopes the ones the program's
own ``jax.named_scope`` calls give its operations, and the step programs
have the names ``trace_reduce`` keys on.  The Python tracer is off (the
launcher's traces have it on): it would make the file thirty times larger
and the readers do not read it.  Writes ``chiprun_out/phase_trace.xplane.pb``
and, beside it, what ``host_spans.load`` and ``trace_reduce.reduce`` made of
it (``phase_trace.expected.json``); the builder copies both to
``benchmarks/data/``.  Needs the chip (a CPU trace has no device plane);
``--platform cpu`` rehearses the script and keeps nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

WIDTHS = dict(vocab_size=2048, hidden_size=512, intermediate_size=1024,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, max_position_embeddings=512)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--platform", default="tpu")
    args = p.parse_args(argv)

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from benchmarks import host_spans, trace_reduce
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (EngineConfig, EngineCore, SamplingParams,
                                    SchedulerConfig)

    if jax.default_backend() != args.platform:
        print(f"record_phase_trace: needs a {args.platform} backend, JAX has "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    paddle.seed(0)
    paddle.set_default_dtype("bfloat16")
    model = LlamaForCausalLM(LlamaConfig(**WIDTHS))
    model.eval()
    engine = EngineCore(model, config=EngineConfig(
        num_blocks=64, block_size=16,
        scheduler=SchedulerConfig(max_num_seqs=4)))
    rng = np.random.default_rng(0)

    def submit(n, lo, hi, new):
        for _ in range(n):
            engine.add_request(
                rng.integers(1, WIDTHS["vocab_size"],
                             int(rng.integers(lo, hi))).tolist(),
                SamplingParams(max_new_tokens=new, temperature=0.7,
                               top_p=0.95, seed=int(rng.integers(1 << 30))))

    # warm every shape the traced steps use: prompts of 17-32 tokens, up to
    # three rows decoding over tables of at most four blocks
    submit(3, 17, 32, 24)
    engine.run(max_steps=200)
    log_dir = os.path.join(os.path.dirname(HERE), ".bench_trace", "phases")
    shutil.rmtree(log_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    submit(2, 17, 32, 10)
    engine.step()
    jax.profiler.start_trace(log_dir, profiler_options=options)
    for _ in range(4):
        engine.step()
    submit(1, 17, 32, 6)        # a prefill between decode steps
    for _ in range(8):
        engine.step()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(log_dir)
    red = trace_reduce.reduce(trace_reduce.load(path))
    got = host_spans.load(path)
    print(json.dumps(got), os.path.getsize(path))
    if args.platform != "tpu":
        return 0
    out = os.path.join(os.path.dirname(HERE), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "phase_trace.xplane.pb"))
    want = {"analysis": got,
            "reduced": {k: red[k] for k in ("busy_s", "gap_s", "launches")},
            "sort_s": sum(v for k, v in red["ops"].items()
                          if k.startswith("sort")),
            "device_kind": jax.devices()[0].device_kind}
    with open(os.path.join(out, "phase_trace.expected.json"), "w") as f:
        json.dump(want, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
