#!/usr/bin/env python3
"""Does the serving main path still start on the chip?

One process, one chip (``--mp 4``: one process, four chips).  Builds a
Llama-3-8B-wide model (published widths, depth cut to fit one v5e and this
script's time limit, seeded random bf16 weights made off-device), serves it
through ``EngineCore`` -> ``FleetRouter`` (fleet of one) ->
``CompletionServer`` over loopback HTTP with client and server in this one
process, and checks what comes out by the repo's own means:

* every request answers 200 with the token count it asked for, the stream
  ends in ``data: [DONE]``;
* decode launches took the compiled Pallas kernel (never interpret mode,
  never the gather path), and nothing warned about a fallback;
* the shadow oracle (``NumericsAuditor``: sampled steps re-executed through
  the XLA gather reference) reports zero divergences at a bf16 tolerance;
* a repeated wave compiles nothing; the drain leaves every pool empty.

Two legs: the default engine configuration, then the unified ragged step
with a token budget and device-resident decode bursts.  Each leg sends its
wave three times: cold (compiles), again (the same prompts now hit the
prefix cache and take the resume programs, which compile once), and a third
time, which must trace nothing.

Exits non-zero, before building anything, unless JAX's default backend is a
TPU.  Times are printed as information, under no metric name.  The last
line of stdout is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py            # the driver's run: one chip
    python chip_smoke.py --mp 4     # the builder's run: 4 chips, 32 layers
"""

from __future__ import annotations

import argparse
import asyncio
import faulthandler
import gc
import http.client
import json
import sys
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEADLINE_S = 1150   # the contract allows 1200 s, compilation included
HTTP_TIMEOUT_S = 1000.0     # a cold wave waits for every compile


@dataclass
class Sizes:
    """Everything the smoke's body depends on (``tests/test_chip_smoke.py``
    drives the same body at ``LlamaConfig.tiny`` on CPU)."""

    model: Dict = field(default_factory=dict)   # LlamaConfig keywords
    mp: int = 1
    num_blocks: int = 2048           # x16 tokens: 32k tokens of KV
    block_size: int = 16
    prompt_lens: Tuple[int, ...] = (900, 200)   # two groups bound the
    requests: int = 8                           # number of bucket programs
    max_tokens: int = 32
    token_budget: int = 256          # unified leg: max_tokens_per_step
    burst_steps: int = 8
    audit_every: int = 32            # each sampled step copies the whole
                                     # pool to the host: sample sparsely
    # bf16 tolerance of the shadow oracle.  Kernel and gather path round
    # the attention output to bf16 in a different order and the error
    # rides through every later layer: on the chip the largest logit
    # difference of an audited step was 0.07 at depth 2, 0.14 at depth 8
    # and 0.20 at depth 16 (logits have a standard deviation of about
    # 1.3); a wrong page or mask moves logits by whole units
    logit_atol: float = 0.5
    logit_rtol: float = 0.05
    use_pallas: Optional[bool] = None   # None: the code selects by what it
                                        # sees; True forces interpret mode
                                        # off the chip (the CPU test)
    # cold compiles; cached: the same prompts hit the prefix cache and take
    # the resume programs, which compile once; repeat must trace nothing
    waves: Tuple[str, ...] = ("cold", "cached", "repeat")


def one_chip(layers: int) -> Sizes:
    """Llama-3-8B widths on one 16 GB v5e: a layer is 436 MB in bf16,
    embeddings + head 2.1 GB, the pool 2.1 GB at 16 layers."""
    return Sizes(model=dict(num_hidden_layers=layers))


def four_chips(layers: int) -> Sizes:
    """Full depth over an mp=4 mesh: 16.1 GB of weights, 4.0 GB a chip,
    pools head-sharded (2 KV heads a chip).  One wave: a chip-minute
    costs four here, and the repeat-wave check is the one-chip run's."""
    return Sizes(model=dict(num_hidden_layers=layers), mp=4,
                 waves=("cold",))


def log(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.perf_counter()


# --- traffic ----------------------------------------------------------------

def make_wave(sizes: Sizes, vocab: int) -> List[Dict]:
    """Seeded request bodies: lengths alternate between the groups (long
    first, so the block-table width bucket is the long group's until only
    the last short request is left), most greedy, two seeded-sampled, one
    streamed."""
    import numpy as np

    rng = np.random.default_rng(0)
    wave = []
    for i in range(sizes.requests):
        n = sizes.prompt_lens[i % len(sizes.prompt_lens)]
        wave.append({"prompt": rng.integers(1, vocab, n).tolist(),
                     "max_tokens": sizes.max_tokens})
    wave[2].update(temperature=0.8, top_k=40, top_p=0.9, seed=1234)
    wave[5].update(temperature=1.0, top_p=0.95, seed=99)
    wave[3]["stream"] = True
    return wave


def send_wave(port: int, wave: List[Dict], vocab: int) -> List[Dict]:
    """Submit the whole wave in order, each request on its own connection,
    then read every answer.  Raises unless every request came back 200
    with exactly the tokens it asked for."""
    conns = []
    for body in wave:
        c = http.client.HTTPConnection("127.0.0.1", port,
                                       timeout=HTTP_TIMEOUT_S)
        c.request("POST", "/v1/completions", json.dumps(body),
                  {"Content-Type": "application/json"})
        conns.append(c)
    answers = []
    for i, (c, body) in enumerate(zip(conns, wave)):
        resp = c.getresponse()
        data = resp.read()
        c.close()
        assert resp.status == 200, \
            f"request {i}: HTTP {resp.status}: {data[:400]!r}"
        if body.get("stream"):
            assert data.endswith(b"data: [DONE]\n\n"), \
                f"request {i}: stream did not end in [DONE]: {data[-200:]!r}"
            events = [json.loads(line[len(b"data: "):])
                      for line in data.split(b"\n\n")
                      if line.startswith(b"data: {")]
            tokens = [t for e in events for t in e["choices"][0]["token_ids"]]
            finish = events[-1]["choices"][0]["finish_reason"]
            usage = events[-1].get("usage", {})
        else:
            obj = json.loads(data)
            tokens = obj["choices"][0]["token_ids"]
            finish = obj["choices"][0]["finish_reason"]
            usage = obj["usage"]
        assert len(tokens) == body["max_tokens"], \
            f"request {i}: {len(tokens)} tokens, asked {body['max_tokens']}"
        assert finish == "length", f"request {i}: finish_reason {finish!r}"
        assert all(0 <= t < vocab for t in tokens), \
            f"request {i}: token id outside [0, {vocab})"
        answers.append({"tokens": tokens,
                        "cached": usage.get("prompt_cached_tokens", 0)})
    return answers


def http_get(port: int, path: str) -> Tuple[int, bytes]:
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    c.request("GET", path)
    resp = c.getresponse()
    data = resp.read()
    c.close()
    return resp.status, data


# --- one leg: an engine behind the real server ------------------------------

def hold_engine_for(replica, n: int) -> None:
    """Keep the replica's (idle) engine thread from planning a step until
    ``n`` requests have reached its queue.  The scheduler then sees every
    wave whole, in list order, so each wave is planned — and bucketed —
    the same way; otherwise which programs a wave needs depends on how
    many requests had arrived when the first step was planned."""
    def hold():
        deadline = time.monotonic() + 60.0
        while replica.submit_q.qsize() < n and time.monotonic() < deadline:
            time.sleep(0.001)

    assert replica.post(hold), "engine task inbox full"


def trace_counts(engine) -> Dict[str, int]:
    return {"prefill": engine.prefill_trace_count,
            "decode": engine.decode_trace_count,
            "ragged": engine.ragged_trace_count,
            "burst": engine.burst_trace_count}


async def serve_leg(name: str, model, sizes: Sizes,
                    engine_kwargs: Dict) -> Dict:
    """Build an engine, put the real server in front of it, send the
    waves and check everything the module docstring promises."""
    import jax.numpy as jnp

    from paddle_tpu.observability.audit import AuditConfig
    from paddle_tpu.serving import EngineConfig, EngineCore
    from paddle_tpu.serving.server import CompletionServer, ServerConfig

    vocab = model.config.vocab_size
    # the shadow oracle re-runs a step on ONE device: at mp>1 that needs
    # the whole model on one chip, which is what mp>1 is there to avoid
    audit = (AuditConfig(enabled=True, sample_every=sizes.audit_every,
                         logit_atol=sizes.logit_atol,
                         logit_rtol=sizes.logit_rtol)
             if sizes.mp == 1 else None)
    t0 = time.perf_counter()
    engine = EngineCore(model, config=EngineConfig(
        num_blocks=sizes.num_blocks, block_size=sizes.block_size,
        dtype=jnp.bfloat16, audit=audit, mp=sizes.mp,
        use_pallas_paged=sizes.use_pallas, **engine_kwargs))
    server = CompletionServer(engine, ServerConfig(port=0))
    await server.start()
    log(f"[{name}] engine + server up in {time.perf_counter() - t0:.1f}s "
        f"(port {server.port})")
    loop = asyncio.get_running_loop()
    wave = make_wave(sizes, vocab)
    out = {"waves": []}
    try:
        status, body = await loop.run_in_executor(
            None, http_get, server.port, "/readyz")
        assert status == 200 and f"dp=1 mp={sizes.mp}".encode() in body, \
            f"/readyz {status}: {body!r}"
        counts = trace_counts(engine)
        for label in sizes.waves:
            t0 = time.perf_counter()
            hold_engine_for(server.fleet.replicas[0], len(wave))
            try:
                answers = await loop.run_in_executor(
                    None, send_wave, server.port, wave, vocab)
            except BaseException:
                if server._engine_error:
                    log(f"[{name}] engine thread died:\n"
                        f"{server._engine_error}")
                raise
            wall = time.perf_counter() - t0
            now = trace_counts(engine)
            traced = {k: now[k] - counts[k] for k in now
                      if now[k] != counts[k]}
            counts = now
            log(f"[{name}] wave {label}: {len(answers)} requests x "
                f"{sizes.max_tokens} tokens in {wall:.1f}s; traced "
                f"{traced or 'nothing'}; prompt tokens from cache "
                f"{sum(a['cached'] for a in answers)}")
            out["waves"].append({"label": label, "wall_s": round(wall, 2),
                                 "traced": traced, "answers": answers})
        for wv in out["waves"]:
            if wv["label"] == "cached":
                assert any(a["cached"] for a in wv["answers"]), \
                    f"[{name}] the second wave never hit the prefix cache"
            if wv["label"] == "repeat":
                assert not wv["traced"], \
                    f"[{name}] the repeated wave traced {wv['traced']}"
        # the path each program family was traced through, as the ops
        # modules reported it (``last_path``) while the engine traced
        out["paths"] = dict(engine.attention_paths)
        log(f"[{name}] attention paths: {out['paths']}")
        status, body = await loop.run_in_executor(
            None, http_get, server.port, "/metrics")
        assert status == 200 and b"serving_time_to_first_token" in body \
            and b"serving_engine_steps_total" in body, \
            f"/metrics {status}: serving series missing"
        if audit is not None:
            status, body = await loop.run_in_executor(
                None, http_get, server.port, "/v1/debug/audit")
            assert status == 200, f"/v1/debug/audit {status}"
            report = json.loads(body)
            row = report["data"][0]
            audited = sum(row["audited_launches"].values())
            log(f"[{name}] shadow oracle: {row['audited_launches']} audited "
                f"launches, divergences {row['divergences']}, oracle "
                f"failures {row['oracle_failures']}, non-finite values "
                f"{row['nonfinite_values']} (atol {sizes.logit_atol}, rtol "
                f"{sizes.logit_rtol}); last: {row['last_divergence']}")
            diffs = engine.audit._absdiff_h
            if diffs is not None and diffs.count:
                log(f"[{name}] shadow oracle max |logit diff| per audited "
                    f"step: mean {diffs.sum / diffs.count:.4f} over "
                    f"{diffs.count} step(s)")
            assert report["status"] == "ok", report
            assert audited > 0, "no launch was shadow-audited"
            assert not any(row["divergences"].values()), row
            assert row["oracle_failures"] == 0, row
            out["audited"] = audited
    finally:
        await server.shutdown(drain_timeout=5.0)
    kv = engine.kv
    assert kv.occupancy() == 0.0 and not kv._tables and not engine.requests \
        and not engine.scheduler.has_work(), \
        f"[{name}] drain left the pool occupied: {kv.occupancy():.4f}"
    log(f"[{name}] drained: pool empty, "
        f"{int(engine.metrics.counters['engine_steps'])} engine steps")
    return out


# --- the body ---------------------------------------------------------------

def memory_report(tag: str) -> List[Dict]:
    import jax

    rows = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        rows.append({"id": d.id, "in_use": st.get("bytes_in_use"),
                     "peak": st.get("peak_bytes_in_use")})
    if rows[0]["peak"] is not None:
        log(f"{tag}: HBM per device (GB) " + ", ".join(
            f"dev{r['id']}: {r['in_use'] / 1e9:.2f} in use / "
            f"{r['peak'] / 1e9:.2f} peak" for r in rows))
    return rows


def run_smoke(sizes: Sizes) -> Dict:
    """The smoke's body, a function of the sizes alone: build, serve both
    legs, check.  Raises on the first failed check."""
    import importlib.metadata as md

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import topology
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import SchedulerConfig
    from paddle_tpu.utils import host_build
    from paddle_tpu.utils.compile_cache import (
        configure_compile_cache,
        count_cache_entries,
    )

    cache_dir = configure_compile_cache()
    entries_before = count_cache_entries(cache_dir)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device {device}; " + ", ".join(
        f"{p} {md.version(p)}" for p in ("jax", "jaxlib", "libtpu")))
    log(f"compile cache {cache_dir}: {entries_before} entries")

    cfg = LlamaConfig(**sizes.model)
    log(f"model: hidden {cfg.hidden_size}, {cfg.num_attention_heads} query / "
        f"{cfg.num_key_value_heads} KV heads of {cfg.head_dim}, FFN "
        f"{cfg.intermediate_size}, vocab {cfg.vocab_size}, DEPTH "
        f"{cfg.num_hidden_layers} layers, mp={sizes.mp}; pool "
        f"{sizes.num_blocks} blocks of {sizes.block_size}; unified leg: "
        f"token budget {sizes.token_budget} a step (every packed token's "
        f"row goes through the full-vocabulary sampler), bursts of "
        f"{sizes.burst_steps}")
    if sizes.mp > 1:
        topology.init_mesh(mp=sizes.mp)     # BEFORE host_build: it shards

    def build():
        paddle.seed(0)
        return LlamaForCausalLM(cfg).bfloat16()

    t0 = time.perf_counter()
    model = host_build(build, log=log)
    jax.block_until_ready([p._value for p in model.parameters()])
    n_params = sum(p.size for p in model.parameters())
    log(f"set-up: built {n_params / 1e9:.2f}B bf16 parameters on the host "
        f"and transferred them in {time.perf_counter() - t0:.1f}s")
    memory_report("after transfer")

    result = {"device": device, "depth": cfg.num_hidden_layers, "legs": {}}
    legs = [
        # the default engine configuration; its decode kernel is
        # single-shard, so under mp>1 the engine pins it to the gather path
        ("default", {}),
        # the program ROADMAP S3/S4/D1 build on
        ("unified+burst",
         dict(unified_step=True, burst_steps=sizes.burst_steps,
              scheduler=SchedulerConfig(
                  max_tokens_per_step=sizes.token_budget))),
    ]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for name, kwargs in legs:
            t0 = time.perf_counter()
            leg = asyncio.run(serve_leg(name, model, sizes, kwargs))
            log(f"[{name}] leg done in {time.perf_counter() - t0:.1f}s "
                "(a wave that traced is compile + serve, one that did not "
                "is serve alone)")
            memory_report(f"[{name}]")
            result["legs"][name] = leg
            gc.collect()    # the leg's pools go before the next leg's come
    fallbacks = [str(w.message) for w in caught
                 if "fall" in str(w.message).lower()]
    assert not fallbacks, f"fallback warnings: {fallbacks}"

    # the kernel ran compiled wherever the engine lets it run at all: the
    # ragged kernel spans the mesh; the decode kernel is single-shard
    decode = "pallas" if sizes.mp == 1 else "xla"
    want = {"default": {"decode": decode},
            "unified+burst": {"ragged": "pallas", "burst": decode}}
    for name, paths in want.items():
        got = result["legs"][name]["paths"]
        assert got == paths, f"[{name}] attention paths {got}, want {paths}"

    rows = memory_report("end")
    if sizes.mp > 1 and rows[0]["in_use"] is not None:
        used = [r["in_use"] for r in rows[:sizes.mp]]
        assert min(used) > 0.95 * max(used), \
            f"per-device bytes_in_use not balanced over the mesh: {used}"
    entries_after = count_cache_entries(cache_dir)
    log(f"compile cache {cache_dir}: {entries_before} entries before, "
        f"{entries_after} after ({entries_after - entries_before} added)")
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--mp", type=int, default=1, choices=(1, 4),
                   help="4: the four-chip leg (mesh mp=4, full depth)")
    p.add_argument("--layers", type=int, default=None,
                   help="depth (default: 16 on one chip, 32 on four); "
                        "widths are never cut")
    args = p.parse_args(argv)
    # the contract's limit binds the driver's one-chip run; the builder's
    # four-chip run compiles twice the depth
    faulthandler.dump_traceback_later(
        DEADLINE_S if args.mp == 1 else 3 * DEADLINE_S, exit=True)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    sizes = (four_chips(args.layers or 32) if args.mp == 4
             else one_chip(args.layers or 16))
    result = run_smoke(sizes)
    log(f"OK: depth {result['depth']}, legs {list(result['legs'])}")
    print(json.dumps({"ok": True, "device": result["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
