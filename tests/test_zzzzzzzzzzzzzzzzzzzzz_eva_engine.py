"""Chunk-summarised (EVA) attention on the serving path, second file (the
first is ``test_zzzzzzzzzzzzzzzzzzzzz_eva.py``): through ``EngineCore``,
rows of mixed lengths in one launch, a slot and blocks reused after a
longer sequence (stale ring entries and summary rows invisible),
preemption by recompute, the loop that runs a launch ahead; the integers
and the ``/metrics`` series; and two faults planted in the PROGRAM."""

import inspect
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from eva_common import (  # noqa: F401  (fixtures among them)
    ATOL,
    RMS_REL,
    TINY,
    builder,
    capture,
    make_engine,
    model,
    prompt_of,
    ref,
    serve,
    served_logits,
)

ROWS = [(90, 30), (10, 40), (40, 30), (3, 70)]     # prompt, new tokens


def summary_rows(eng):
    """The engine's telemetry object of the ring-and-rows kind."""
    from paddle_tpu.ops.eva_attention import SummaryRows

    (kind,) = (t for t in eng._telemetry if isinstance(t, SummaryRows))
    return kind


@pytest.fixture(scope="module")
def alone(model):
    """Each request of ROWS served alone, on one engine, one at a time."""
    eng = make_engine(model)
    return [list(serve(eng, prompt_of(n, seed=100 + i), new - 1).output_tokens)
            for i, (n, new) in enumerate(ROWS)]


def crowd(eng, steps=600):
    from paddle_tpu.serving.request import SamplingParams

    reqs = [eng.add_request(prompt_of(n, seed=100 + i), SamplingParams(
        max_new_tokens=new, temperature=0.0))
        for i, (n, new) in enumerate(ROWS)]
    for _ in range(steps):
        if all(r.finished for r in reqs):
            break
        eng.step()
    assert all(r.finished for r in reqs)
    return reqs


def test_rows_of_mixed_lengths_and_reused_slots_give_the_same_tokens(
        model, alone):
    """Two slots for four requests: rows 90+ and 10+ bytes long share
    every decode launch (three closed windows' rows beside none), and the
    third and fourth take the slots and blocks the first two leave dirty
    -- a sequence SHORTER than what its slot's ring and its blocks' rows
    last held."""
    from paddle_tpu.serving import SchedulerConfig

    eng = make_engine(model, num_blocks=20,
                      scheduler=SchedulerConfig(max_num_seqs=2))
    reqs = crowd(eng)
    assert [list(r.output_tokens) for r in reqs] == alone
    assert float(jnp.abs(eng._k_pools[0][0][1:]).max()) > 0     # rings dirty
    assert float(jnp.abs(eng._k_pools[0][1]).max()) > 0         # rows dirty
    assert eng.kv.state_slots_held == 0 and eng.kv.num_free == 19


def test_preemption_by_recompute_gives_the_same_tokens(model, alone):
    """A pool too small for four rows to finish: a victim's ring and rows
    are rebuilt by a prefill of its prompt and what it had generated (a
    prompt of several windows: by windows)."""
    tight = make_engine(model, num_blocks=15)   # 14 blocks = 224 bytes
    reqs = crowd(tight)
    reg, labels = tight.metrics.registry, tight.metrics.labels
    assert reg.counter("serving_preemptions_total", **labels).value > 0
    assert [list(r.output_tokens) for r in reqs] == alone
    assert tight.kv.num_free == 14


def test_the_loop_that_runs_ahead_serves_the_tokens_of_bare_steps(model):
    from run_ahead_common import (ARRIVALS, ahead_counts, assert_clean,
                                  drive, outputs)

    want = outputs(drive(make_engine(model), False, ARRIVALS))
    eng = make_engine(model)
    assert outputs(drive(eng, True, ARRIVALS)) == want
    assert ahead_counts(eng)["launches"] > 0
    assert_clean(eng)


def test_the_integers_of_a_decode_launch_and_the_metrics_series(model):
    """``engine.build`` of a decode launch carries, over its rows at
    positions p: ring entries ``(p mod 32) + 1``, summary rows ``2 (p //
    32)``, the rows whose window this byte closes, the rows held after
    it; ``/metrics`` has the gauge and the counter."""
    eng = make_engine(model)
    seen = []
    orig = eng.tracer.phase

    def phase(name, prof=None, **stats):
        if name == "engine.build" and "eva_ring_tokens" in stats:
            seen.append(stats)
        return orig(name, prof, **stats)

    eng.tracer.phase = phase
    serve(eng, prompt_of(61, seed=1), 5)        # decode at p = 61 .. 65
    want = [{"eva_ring_tokens": p % 32 + 1, "eva_summary_rows": 2 * (p // 32),
             "eva_windows_closed": int((p + 1) % 32 == 0),
             "eva_rows_held": (p + 1) // 16} for p in range(61, 66)]
    assert [{k: s[k] for k in want[0]} for s in seen] == want
    assert all(s["state_rows"] == 1 and "window_tokens" not in s
               for s in seen)
    reg, labels = eng.metrics.registry, eng.metrics.labels
    # the prompt closed one window (61 // 32), decode a second at p = 63
    assert reg.counter("serving_eva_windows_closed_total",
                       **labels).value == 2
    assert reg.gauge("serving_eva_summary_rows_held", **labels).value == 4
    text = reg.prometheus_text()
    assert "serving_eva_summary_rows_held" in text
    # a model without such layers has neither
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    dense = make_engine(LlamaForCausalLM(LlamaConfig.tiny()))
    assert dense._telemetry == []       # a dense layer brings none
    assert "serving_eva_" not in dense.metrics.registry.prometheus_text()


@pytest.mark.parametrize("blocks", [40, 41])
def test_the_tiles_a_launch_sees_are_counted_from_the_tables(
        model, monkeypatch, blocks):
    """``engine.build`` of a decode launch carries ``eva_pool_tiles_seen``
    / ``eva_pool_tiles``: the tiles of a layer's pool of rows in which
    some row of the launch sees a row (a closed window's), of the tiles
    there are -- counted here by hand from the tables --, and ``/metrics``
    sums both over launches; the integers the roofline reads are as they
    were.  The rows past a pool's last whole tile (41 blocks: two of them)
    are read by every launch and count as a tile seen."""
    from paddle_tpu.ops import pallas_eva
    from paddle_tpu.serving.request import SamplingParams

    # tiles of 4 rows, so that three sequences' blocks spread over several
    monkeypatch.setattr(pallas_eva, "pool_tile_rows", lambda *a: 4)
    eng = make_engine(model, block_size=32, num_blocks=blocks)
    W, C, R, T = 32, 16, 2, 4
    kind = summary_rows(eng)
    assert kind.tile_rows == T
    total, rest = -(-blocks * R // T), {blocks * R // T} if blocks % 2 else set()
    launches, built = [], []
    ints, phase = kind.build_ints, eng.tracer.phase

    def counted(view, n, reqs):
        if view.program != "decode":
            return ints(view, n, reqs)
        rows = [(eng.kv.seq_len(r.request_id), list(eng.kv.table(
            r.request_id))) for r in reqs]
        launches.append((ints(view, n, reqs), rows))
        return launches[-1][0]

    def traced(name, prof=None, **stats):
        if name == "engine.build" and "eva_ring_tokens" in stats:
            built.append(stats)
        return phase(name, prof, **stats)

    kind.build_ints, eng.tracer.phase = counted, traced
    reqs = [eng.add_request(prompt_of(n, seed=n), SamplingParams(
        max_new_tokens=40, temperature=0.0)) for n in (20, 70, 130)]
    for _ in range(200):
        if all(r.finished for r in reqs):
            break
        eng.step()
    assert all(r.finished for r in reqs)
    assert max(len(rows) for _, rows in launches) == 3
    for got, rows in launches:
        tiles = rest | {(table[c // R] * R + c % R) // T for p, table in rows
                        for c in range((W // C) * (p // W))}
        ps = [p for p, _ in rows]
        assert got == {
            "eva_pool_tiles_seen": len(tiles), "eva_pool_tiles": total,
            "eva_ring_tokens": sum(p % W + 1 for p in ps),
            "eva_summary_rows": sum((W // C) * (p // W) for p in ps),
            "eva_windows_closed": sum((p + 1) % W == 0 for p in ps),
            "eva_rows_held": sum((p + 1) // C for p in ps)}, (got, rows)
    # the rows' windows close as they decode: the tiles seen grow
    seen = [got["eva_pool_tiles_seen"] for got, _ in launches]
    assert len(rest) == min(seen) < max(seen) <= total
    assert [{k: s[k] for k in launches[0][0]} for s in built] \
        == [got for got, _ in launches]
    reg, labels = eng.metrics.registry, eng.metrics.labels
    assert reg.counter("serving_eva_pool_tiles_seen_total",
                       **labels).value == sum(seen)
    assert reg.counter("serving_eva_pool_tiles_total",
                       **labels).value == total * len(launches)


def test_a_preempted_rows_tiles_are_counted_from_its_new_blocks(model,
                                                               monkeypatch):
    """A row's tiles are kept from launch to launch under (windows closed,
    first block).  The free list is last-in-first-out: a row preempted and
    prefilled again between two decode launches may get its FIRST block
    back and other later ones, so the admission that preempts it drops
    what was kept and the next launch counts from the new table."""
    import types

    from paddle_tpu.ops import pallas_eva

    monkeypatch.setattr(pallas_eva, "pool_tile_rows", lambda *a: 4)
    eng = make_engine(model, block_size=32, num_blocks=40)
    W, C, R, T = 32, 16, 2, 4
    req = types.SimpleNamespace(request_id="r", trace_id=None,
                                output_tokens=[1, 2])
    tables = {"r": [3, 4, 5, 6, 7]}
    monkeypatch.setattr(eng.kv, "table", lambda rid: tables[rid])
    monkeypatch.setattr(eng.kv, "seq_len", lambda rid: 4 * W + 5)

    def by_hand():
        return len({(tables["r"][c // R] * R + c % R) // T
                    for c in range((W // C) * 4)})

    def seen():
        return eng._build_ints("decode", 1, [req])["eva_pool_tiles_seen"]

    assert seen() == by_hand() == 3
    tables["r"] = [3, 20, 9, 30, 31]
    assert by_hand() == 4
    eng._admit(types.SimpleNamespace(preempted=[req], aborted=[],
                                     admitted=[]))
    assert seen() == 4


def test_the_metrics_are_documented_where_the_checker_looks():
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "tools/check_metrics_docs.py"],
                         cwd=root, capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    readme = open(os.path.join(root, "README.md")).read()
    for name in ("serving_eva_summary_rows_held",
                 "serving_eva_windows_closed_total",
                 "serving_eva_pool_tiles_seen_total",
                 "serving_eva_pool_tiles_total"):
        assert name in readme


# --- faults planted in the program --------------------------------------------------

def planted(monkeypatch, fn, old, new):
    """``fn`` of ``ops/eva_attention.py`` with ``old`` replaced by
    ``new`` in its source."""
    from paddle_tpu.ops import eva_attention as eva

    src = textwrap.dedent(inspect.getsource(getattr(eva, fn)))
    assert src.count(old) == 1, (fn, old)
    scope = dict(vars(eva))
    exec(compile(src.replace(old, new), f"<planted {fn}>", "exec"), scope)
    monkeypatch.setattr(eva, fn, scope[fn])


def swap_pooling(monkeypatch):
    from paddle_tpu.ops import eva_attention as eva

    real = eva.pool_chunks
    monkeypatch.setattr(eva, "pool_chunks",
                        lambda k, v, mu, phi: real(k, v, phi, mu))


PLANTS = {
    "none": lambda mp: None,
    "mu_and_phi_swapped": swap_pooling,
    "decode_reads_a_sliding_window": lambda mp: planted(
        mp, "_decode_attention_xla", "n_loc = jnp.mod(pos, W) + 1",
        "n_loc = jnp.minimum(pos + 1, W)"),
    "decode_sees_summaries_a_window_early": lambda mp: planted(
        mp, "_seen_rows", "n_rem = (window // chunk) * (pos // window)",
        "n_rem = (window // chunk) * (pos // window + 1)"),
    "a_prompts_windows_see_no_summaries": lambda mp: planted(
        mp, "span_attention", "< qw[:, None] // window)",
        "< qw[:, None] // window - 1)"),
    "stale_summary_rows_visible": lambda mp: planted(
        mp, "_seen_rows", "n_rem = (window // chunk) * (pos // window)",
        "n_rem = (window // chunk) * (pos // window) + 2 * (pos >= 64)"),
}


@pytest.mark.parametrize("fault", list(PLANTS))
def test_a_fault_planted_in_the_program_fails_the_comparison(
        ref, builder, model, fault, monkeypatch):
    """One request dirties a slot and its blocks, then a prompt of 70
    bytes (three windows, by windows) and 30 decode steps over the fourth
    window's start, every launch compared with the reference."""
    PLANTS[fault](monkeypatch)
    eng = make_engine(model, num_blocks=16)
    serve(eng, prompt_of(120, seed=9), 4)
    rows = capture(eng)
    prompt = prompt_of(70, seed=70)
    req = serve(eng, prompt, 30)
    seq = prompt + [int(t) for t in req.output_tokens[:30]]
    full = np.asarray(ref.reference_logits(
        builder.reference_weights(model), TINY, seq))
    res = ref.compare(served_logits(rows, 30), full[69:], ATOL, RMS_REL)
    assert res["ok"] == (fault == "none"), (fault, res)
