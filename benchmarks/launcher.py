#!/usr/bin/env python3
"""The child that holds the chip.  ``run.py`` starts exactly one.

Builds the cell's configuration (seeded weights made on the device), wires
``EngineCore -> FleetRouter(1) -> CompletionServer`` as ``chip_smoke.py``
does, checks the model against the configuration's plain reference at
logit level, warms up the step programs the cell's traffic can reach and no
others, serves over loopback HTTP, and answers the client's commands.

Commands arrive as JSON lines on stdin, answers leave as lines starting
``@@ `` on stdout (anything else on stdout is the program's own chatter):

``{"cmd": "mark", "name": "open" | "close" | "trace"}``
    snapshot the program's counters now; ``trace`` runs the JAX profiler
    for ``--trace-seconds`` (on an accelerator) and snapshots before and
    after; the ``.xplane.pb`` it leaves is read by the client, once this
    process has stopped;
``{"cmd": "report"}``
    waits for the profiler, then everything gathered, as one JSON object;
    ``{"cmd": "quit"}``.

What is taken from the program: the system under test, its counters
(``StepProfiler``, ``CacheStatTracker``'s pool, the serving registry) and
the names its programs have in the trace.  What is the benchmark's own:
the probe around ``EngineCore._step_call`` (rows and cache lengths of every
launch, and the logits of the reference check), installed from outside.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import shutil
import sys
import threading
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def log(msg: str) -> None:
    print(f"[launcher +{time.perf_counter() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def say(obj) -> None:
    print("@@ " + json.dumps(obj), flush=True)


# --- the probe -----------------------------------------------------------------

class ProbeMismatch(RuntimeError):
    """The program's step call no longer looks the way the probe reads it."""


def bind_step_args(jit_fn, args) -> dict:
    """A step program's arguments BY NAME, from the signature of the jitted
    function itself: a program PR that reorders them changes nothing here,
    and one that renames or drops a name the probe reads fails the run in
    set-up instead of miscounting."""
    import inspect

    try:
        return inspect.signature(jit_fn).bind(*args).arguments
    except TypeError as e:
        raise ProbeMismatch(f"step program {jit_fn}: {e}") from None


def take(named: dict, program: str, name: str):
    if name not in named:
        raise ProbeMismatch(f"step program {program!r} takes no argument "
                            f"{name!r} any more; it takes {list(named)}")
    return named[name]


class Probe:
    """Counts what each step-program launch was given, from outside: for
    every program family its launches, and where the family routes through
    block tables (decode, and the chunk, ragged and burst programs of cells
    to come) its real rows and the cache tokens they had to read."""

    def __init__(self, engine, vocab: int):
        import numpy as np

        self.engine = engine
        self.orig = engine._step_call
        self.n = {"decode_launches": 0, "decode_rows": 0, "decode_kv_tokens": 0,
                  "prefill_launches": 0, "prefill_tokens": 0,
                  "prefill_tokens_sq": 0}
        self.pool_peak = 0.0
        self.rows_hist = {}
        self.capture = None

        def bump(key, by):
            self.n[key] = self.n.get(key, 0) + by

        def call(program, bucket, jit_fn, *args):
            out = self.orig(program, bucket, jit_fn, *args)
            named = bind_step_args(jit_fn, args)
            ids = np.shape(take(named, program, "ids"))
            bump(f"{program}_launches", 1)
            if program == "prefill":
                last = np.asarray(take(named, program, "last_pos"))
                if last.ndim or last.dtype.kind != "i" \
                        or not 0 <= int(last) < ids[-1]:
                    raise ProbeMismatch(f"prefill last_pos {last!r} is not a "
                                        f"position inside {ids}")
                tokens = int(last) + 1
                bump("prefill_tokens", tokens)
                bump("prefill_tokens_sq", tokens * tokens)
            else:
                lens = np.asarray(take(named, program, "lens"))
                tables = np.shape(take(named, program, "tables"))
                if lens.ndim != 1 or lens.dtype.kind != "i" \
                        or len(tables) != 2 or tables[0] != lens.shape[0] \
                        or (program == "decode"
                            and lens.shape[0] != tuple(bucket)[0]):
                    raise ProbeMismatch(
                        f"{program} lens {lens.dtype}{lens.shape} / tables "
                        f"{tables} are not one cache length a row of bucket "
                        f"{bucket}")
                real = lens > 1         # padding rows hold 1 null token
                rows = int(real.sum())
                bump(f"{program}_rows", rows)
                bump(f"{program}_kv_tokens", int(lens[real].sum()))
                if program == "decode":
                    self.rows_hist[rows] = self.rows_hist.get(rows, 0) + 1
            logits = out[1]
            if len(out) != 5 or np.ndim(logits) not in (1, 2) \
                    or np.shape(logits)[-1] != vocab \
                    or np.dtype(logits.dtype).kind != "f":
                raise ProbeMismatch(
                    f"{program} returned {len(out)} values, the second of "
                    f"shape {np.shape(logits)}: not (tokens, logits[.., "
                    f"{vocab}], stats, k_pools, v_pools)")
            self.pool_peak = max(self.pool_peak, engine.kv.occupancy())
            if self.capture is not None:
                self.capture.append((program, np.asarray(logits, np.float32)))
            return out

        engine._step_call = call

    def snapshot(self) -> dict:
        eng = self.engine
        reg, labels = eng.metrics.registry, eng.metrics.labels
        qw = reg.histogram("serving_queue_wait_seconds", **labels)
        programs = {f"{r['program']}|{r['bucket']}":
                    [r["launches"], r["scheduled_tokens"], r["capacity_tokens"]]
                    for r in eng.stepprof.program_table()}
        peak, self.pool_peak = self.pool_peak, 0.0
        return {
            "t": time.perf_counter(), "probe": dict(self.n),
            "rows_hist": dict(self.rows_hist), "programs": programs,
            "traces": (eng.prefill_trace_count + eng.decode_trace_count
                       + eng.ragged_trace_count + eng.burst_trace_count),
            "queue_wait": [qw.sum, qw.count],
            "preemptions": reg.counter("serving_preemptions_total",
                                       **labels).value,
            "pool_peak_before": peak,
        }


def diff(a: dict, b: dict) -> dict:
    """What happened between two snapshots."""
    progs = {}
    for k, v in b["programs"].items():
        v0 = a["programs"].get(k, [0, 0, 0])
        d = [x - y for x, y in zip(v, v0)]
        if d[0]:
            progs[k] = d
    hist = {k: v - a["rows_hist"].get(k, 0) for k, v in b["rows_hist"].items()
            if v - a["rows_hist"].get(k, 0)}
    return {
        "seconds": b["t"] - a["t"],
        "probe": {k: v - a["probe"].get(k, 0) for k, v in b["probe"].items()},
        "rows_hist": hist, "programs": progs,
        "compiles": b["traces"] - a["traces"],
        "queue_wait_s": b["queue_wait"][0] - a["queue_wait"][0],
        "queue_wait_n": b["queue_wait"][1] - a["queue_wait"][1],
        "preemptions": b["preemptions"] - a["preemptions"],
        "pool_peak_share": b["pool_peak_before"],
    }


# --- set-up --------------------------------------------------------------------

def check_reference(engine, probe, model, builder, ref, cfg, seed) -> dict:
    """Prefill, then decode steps through the paged cache, against the plain
    reference's full forward pass: logits, not tokens."""
    import numpy as np

    from paddle_tpu.serving.request import SamplingParams

    chk = cfg["check"]
    steps = int(chk["decode_steps"])
    rng = np.random.default_rng(seed)
    vocab = cfg["vocab_size"]
    weights = builder.reference_weights(model)
    got, want = [], []
    for n in chk["prompt_lens"]:
        prompt = rng.integers(1, vocab, int(n)).tolist()
        probe.capture = []
        req = engine.add_request(prompt, SamplingParams(
            max_new_tokens=steps + 1, temperature=0.0))
        for _ in range(steps + 8):
            if req.finished:
                break
            engine.step()
        rows, probe.capture = probe.capture, None
        if not req.finished or len(rows) != steps + 1:
            raise RuntimeError(f"reference check: {len(rows)} launches for "
                               f"{steps + 1} tokens (finished {req.finished})")
        for program, logits in rows:
            got.append(logits if logits.ndim == 1 else logits[0])
        ids = prompt + [int(t) for t in req.output_tokens[:steps]]
        full = ref.reference_logits(weights, cfg, ids)
        want.append(np.asarray(full[len(prompt) - 1:], np.float32))
    out = ref.compare(np.stack(got), np.concatenate(want),
                      float(chk["atol"]), float(chk["rms_rel"]))
    out["prompt_lens"] = list(chk["prompt_lens"])
    out["decode_steps"] = steps
    return out


def needed_buckets(engine, limits: dict) -> list:
    """The (program, bucket) shapes this cell's traffic can reach, out of
    the engine's own closed set (``aot.enumerate_buckets``)."""
    from paddle_tpu.serving.aot import enumerate_buckets
    from paddle_tpu.serving.scheduler import bucket_size

    bs = engine.block_size
    sched = engine.scheduler.config
    rows_cap = min(sched.max_num_seqs, limits["in_flight"] or sched.max_num_seqs)
    pool_tokens = (engine.num_blocks - 1) * bs
    # only a bounded backlog can be reckoned to fill the pool; an open loop
    # below its knee that preempts shows as a compile inside the window
    preempt = (limits["in_flight"] is not None
               and pool_tokens < rows_cap * limits["max_total"])
    top = max(limits["max_prompt"],
              limits["max_total"] - 1 if preempt else 0)
    p_lo, p_hi = bucket_size(limits["min_prompt"]), bucket_size(top)
    w_lo = bucket_size(math.ceil(limits["min_total"] / bs))
    w_hi = bucket_size(math.ceil(limits["max_total"] / bs))
    r_hi = bucket_size(rows_cap)
    resume = preempt and engine.engine_config.prefix_cache
    out = []
    for program, b in enumerate_buckets(engine, limits["max_total"]):
        if program == "prefill" and p_lo <= b[0] <= p_hi:
            out.append((program, b))
        elif program == "decode" and b[0] <= r_hi and w_lo <= b[1] <= w_hi:
            out.append((program, b))
        elif program == "chunk" and resume and b[0] <= p_hi \
                and w_lo <= b[1] <= w_hi:
            out.append((program, b))
    return out


def warm(engine, call, buckets: list) -> None:
    """Run every needed program once on padding alone (every row and token
    routed to block 0, the null page), with arguments of exactly the types
    the engine's dispatch sites build, so the window traces nothing.  The
    arguments are matched to the program's own signature by name: one the
    benchmark does not know is an error, not a program warmed wrongly."""
    import inspect

    import numpy as np

    from paddle_tpu.serving.sampling import SamplingPack

    def zeros(*shape):
        return np.zeros(shape, np.int32)

    for program, b in buckets:
        if program == "decode":
            rows, width = b
            fn, given = engine._jit_decode, {
                "ids": np.zeros((rows, 1), np.int64), "pos": zeros(rows),
                "tables": zeros(rows, width), "lens": np.ones((rows,), np.int32),
                "slot_blocks": zeros(rows), "slot_offsets": zeros(rows)}
        elif program == "prefill":
            (tokens,) = b
            rows, fn, given = 1, engine._jit_prefill, {
                "ids": np.zeros((1, tokens), np.int64), "last_pos": np.int32(0),
                "blocks": zeros(tokens),
                "offs": (np.arange(tokens) % engine.block_size).astype(np.int32)}
        else:   # chunk
            chunk, width = b
            rows, fn, given = 1, engine._jit_chunk_prefill, {
                "ids": np.zeros((1, chunk), np.int64), "start": np.int32(0),
                "last_pos": np.int32(0), "tables": zeros(1, width),
                "lens": np.array([1], np.int32), "slot_blocks": zeros(1, chunk),
                "slot_offsets": zeros(1, chunk)}
        given.update(zip(("temps", "top_ks", "top_ps", "keys"),
                         SamplingPack(rows).arrays()),
                     param_vals=engine._param_vals(), k_pools=engine._k_pools,
                     v_pools=engine._v_pools)
        names = list(inspect.signature(fn).parameters)
        if set(names) != set(given):
            raise ProbeMismatch(f"step program {program!r} takes {names}; the "
                                f"benchmark's warm-up knows {sorted(given)}")
        toks, _, _, engine._k_pools, engine._v_pools = call(
            program, b, fn, *(given[n] for n in names))
        np.asarray(toks)


def device_info(jax) -> dict:
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


# --- serving and commands ------------------------------------------------------

class Session:
    def __init__(self, engine, probe, trace_seconds: float, trace_dir: str,
                 device_trace: bool):
        self.engine, self.probe = engine, probe
        self.trace_seconds, self.trace_dir = trace_seconds, trace_dir
        self.device_trace = device_trace
        self.marks = {}
        self._trace_thread = None

    def mark(self, name: str) -> None:
        if name != "trace":
            self.marks[name] = self.probe.snapshot()
            return
        self._trace_thread = threading.Thread(target=self._run_trace,
                                              daemon=True)
        self._trace_thread.start()

    def _run_trace(self) -> None:
        """The profiler, started and stopped from this thread.  Not through
        ``StepProfiler.arm_capture``: that holds the profiler's lock, which
        every engine step takes, across ``start_trace`` and ``stop_trace``,
        and stalled serving for ~20 s (my chip run, PR 23).  The Python
        tracer is off: no reader reads its events, and under it a host
        chain of 256 rows is two to seven times too long, so the idle
        share and every ``engine.gap_*`` ranked work an untraced server
        does not have (PERF.md section 6, PR 42)."""
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        if self.device_trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
        # counters between the profiler's start and the call that stops it
        self.marks["trace0"] = self.probe.snapshot()
        time.sleep(self.trace_seconds)
        self.marks["trace1"] = self.probe.snapshot()
        if self.device_trace:
            jax.profiler.stop_trace()

    def report(self, jax) -> dict:
        if self._trace_thread is not None:
            self._trace_thread.join(120.0)
        out = {"device": device_info(jax), "marks": {}}
        m = self.marks
        if "open" in m and "close" in m:
            out["window"] = diff(m["open"], m["close"])
        if "trace0" in m and "trace1" in m:
            # the trace itself is read by the client once this process has
            # stopped: read here it was parsed beside an engine and a loop
            # thread still serving the backlog (PERF.md section 6, PR 42)
            out["traced"] = diff(m["trace0"], m["trace1"])
        return out


async def serve(session: Session, engine, max_queue: int, ready: dict, jax):
    from paddle_tpu.serving.server import CompletionServer, ServerConfig

    server = CompletionServer(engine, ServerConfig(port=0, max_queue=max_queue))
    await server.start()
    loop = asyncio.get_running_loop()
    say(dict(ready, event="ready", port=server.port))
    try:
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            if not line:
                break
            cmd = json.loads(line)
            if cmd["cmd"] == "mark":
                session.mark(cmd["name"])
                say({"event": "marked", "name": cmd["name"]})
            elif cmd["cmd"] == "report":
                rep = await loop.run_in_executor(None, session.report, jax)
                say(dict(rep, event="report"))
            elif cmd["cmd"] == "quit":
                break
    finally:
        if server._engine_error:
            log(f"engine thread died:\n{server._engine_error}")
        await server.shutdown(drain_timeout=0.5)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--root", required=True)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--trace-seconds", type=float, default=0.0)
    p.add_argument("--platform", default="tpu")
    args = p.parse_args(argv)

    import jax

    from benchmarks import harness

    cell = harness.Cell(args.workload, args.root)
    if jax.default_backend() != args.platform \
            or len(jax.devices()) < cell.chips:
        print(f"benchmark: cell {cell.name} needs {cell.chips} "
              f"{args.platform} chip(s); JAX has {len(jax.devices())} "
              f"{jax.default_backend()!r} device(s)", file=sys.stderr)
        return 2

    import jax.numpy as jnp

    from paddle_tpu.serving import EngineConfig, EngineCore, SchedulerConfig
    from paddle_tpu.utils.compile_cache import (configure_compile_cache,
                                                count_cache_entries)

    cache_dir = configure_compile_cache()
    entries = count_cache_entries(cache_dir)
    split = {"start_s": time.perf_counter() - T0}
    cfg, eng = cell.config, cell.config["engine"]
    builder = cell.module("models", cfg["builder"])
    ref = cell.module("reference", cfg["reference"])

    t = time.perf_counter()
    model = builder.build(cfg, args.seed)
    jax.block_until_ready([q._value for q in model.parameters()])
    split["build_s"] = time.perf_counter() - t
    log(f"built {sum(q.size for q in model.parameters()) / 1e9:.2f}B "
        f"parameters on {jax.devices()[0].device_kind} in "
        f"{split['build_s']:.1f}s; compile cache {cache_dir}: {entries} entries")

    t = time.perf_counter()
    engine = EngineCore(model, config=EngineConfig(
        num_blocks=eng["num_blocks"], block_size=eng["block_size"],
        dtype=jnp.dtype(eng["pool_dtype"]),
        prefix_cache=eng["prefix_cache"],
        scheduler=SchedulerConfig(max_num_seqs=eng["max_num_seqs"])))
    probe = Probe(engine, cfg["vocab_size"])
    split["engine_s"] = time.perf_counter() - t

    t = time.perf_counter()
    check = check_reference(engine, probe, model, builder, ref, cfg, args.seed)
    split["check_s"] = time.perf_counter() - t
    log(f"reference check: {check}")

    t = time.perf_counter()
    buckets = needed_buckets(engine, harness.traffic_limits(cell.traffic))
    traces0 = engine.prefill_trace_count + engine.decode_trace_count
    warm(engine, probe.orig, buckets)
    split["warm_s"] = time.perf_counter() - t
    split["warm_programs"] = len(buckets)
    split["warm_traced"] = (engine.prefill_trace_count
                            + engine.decode_trace_count - traces0)
    split["cache_entries_before"] = entries
    split["cache_entries_after"] = count_cache_entries(cache_dir)
    split["cache_mb"] = sum(
        e.stat().st_size for e in os.scandir(cache_dir) if e.is_file()) / 1e6
    log(f"warmed {len(buckets)} programs in {split['warm_s']:.1f}s "
        f"({split['warm_traced']} traced); cache entries {entries} -> "
        f"{split['cache_entries_after']} ({split['cache_mb']:.0f} MB)")

    session = Session(engine, probe, args.trace_seconds,
                      os.path.join(args.root, ".bench_trace"),
                      device_trace=args.platform == "tpu")
    ready = {"check": check, "split": split, "device": device_info(jax)}
    asyncio.run(serve(session, engine, int(eng["max_queue"]), ready, jax))
    return 0


if __name__ == "__main__":
    sys.exit(main())
