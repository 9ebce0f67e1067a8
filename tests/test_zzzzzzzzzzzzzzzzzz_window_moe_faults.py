"""Window layers on the serving path, second file (the first is
``test_zzzzzzzzzzzzzzzzzz_window_moe.py``): the same tokens under
preemption by recompute, from a ring another sequence left dirty, alone
and in a crowd, and from the loop that runs a launch ahead; and the faults
the comparison with the plain reference must catch."""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from window_moe_common import (  # noqa: F401  (fixtures among them)
    ATOL,
    KINDS,
    PERIOD,
    RMS_REL,
    TINY,
    builder,
    capture,
    check,
    make_engine,
    model,
    period,
    prompt_of,
    ref,
    serve,
)


def test_preemption_by_recompute_gives_the_same_tokens(period):
    from paddle_tpu.serving.request import SamplingParams

    calm = make_engine(period)
    prompts = [prompt_of(14, seed=s) for s in range(4)]
    want = [serve(calm, p, 12).output_tokens for p in prompts]
    tight = make_engine(period, num_blocks=26)   # 17 common blocks = 68 tokens
    reqs = [tight.add_request(p, SamplingParams(max_new_tokens=13,
                                                temperature=0.0))
            for p in prompts]
    held = []
    for _ in range(400):
        if all(r.finished for r in reqs):
            break
        tight.step()
        held.append(tight.kv.state_slots_held)
    reg, labels = tight.metrics.registry, tight.metrics.labels
    assert reg.counter("serving_preemptions_total", **labels).value > 0
    assert [r.output_tokens for r in reqs] == want
    assert min(held[:-1]) < 4 and held[-1] == 0
    assert tight.kv.num_free == 25


def test_a_ring_reused_after_a_finished_sequence_reads_nothing_left(period):
    eng = make_engine(period)
    first = serve(eng, prompt_of(30, seed=5), 9)
    slot = eng.kv._free_slots[-1]               # the one handed out next
    assert float(jnp.abs(eng._k_pools[0][slot]).max()) > 0    # left dirty
    prompt = prompt_of(5, seed=6)               # shorter than the window
    again = serve(eng, prompt, 9)
    fresh = serve(make_engine(period), prompt, 9)
    assert again.output_tokens == fresh.output_tokens
    assert first.output_tokens != again.output_tokens


def test_a_request_alone_and_in_a_crowd_gives_the_same(period):
    """Its prefill is the same one-row program both times, so those logits
    agree bit for bit in float32; the decode rows run in another row
    bucket, so there the tokens are what is compared."""
    from paddle_tpu.serving.request import SamplingParams

    prompt = prompt_of(21, seed=11)
    alone = make_engine(period, num_blocks=256)
    rows = capture(alone)
    req = serve(alone, prompt, 12)
    want = rows[0][1]

    crowd = make_engine(period, num_blocks=256)
    rows = capture(crowd)
    greedy = SamplingParams(max_new_tokens=13, temperature=0.0)
    for s in range(7):
        crowd.add_request(prompt_of(5 + 3 * s, seed=20 + s), greedy)
    mine = crowd.add_request(prompt, greedy)
    for _ in range(120):
        if mine.finished:
            break
        crowd.step()
    assert mine.output_tokens == req.output_tokens
    assert max(l.shape[0] for p, l in rows if p == "decode") == 8
    assert any((l == want).all() for p, l in rows if p == "prefill")


def test_the_loop_that_runs_ahead_serves_the_tokens_of_bare_steps(period):
    """The serving loop's step: decode launch N+1 is built from positions
    and dispatched while N is on the device; a ring is written at its
    row's position whichever launch reads it next."""
    from run_ahead_common import (ARRIVALS, ahead_counts, assert_clean,
                                  drive, outputs)

    want = outputs(drive(make_engine(period), False, ARRIVALS))
    eng = make_engine(period)
    assert outputs(drive(eng, True, ARRIVALS)) == want
    assert ahead_counts(eng)["launches"] > 0
    assert_clean(eng)


# --- the faults the comparison must catch ----------------------------------------

FAULTS = ["none", "window_off_by_one", "global_layer_rotated",
          "window_layer_not_rotated", "shared_experts_summed",
          "sequential_block", "rms_for_layer_norm", "stale_ring",
          "order_shifted"]
@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_fails_the_comparison(ref, builder, period, fault,
                                            monkeypatch):
    """One request dirties the rings, then a 21-token prompt (past the
    window, 11 pad positions in its bucket) and 12 decode steps through
    rings and pages, every launch compared."""
    from paddle_tpu.models import llama, window_moe
    from paddle_tpu.ops import window_attention as wa

    cfg = dict(PERIOD)
    if fault == "order_shifted":
        cfg["layer_types"] = KINDS[1:] + KINDS[:1]
    broken = builder.build(cfg, 7, dtype="float32")
    layers = broken.llama.layers
    if fault == "window_off_by_one":        # the prompt's mask sees 9 keys
        for layer in layers:
            if layer.window:
                layer.self_attn.window = layer.window + 1
    elif fault == "global_layer_rotated":
        for layer in layers:
            if not layer.window:
                layer.self_attn._rope = llama._rope_tables(16, 256, 1e4)
    elif fault == "window_layer_not_rotated":
        monkeypatch.setattr(window_moe, "_apply_rope", lambda x, c, s: x)
    elif fault == "shared_experts_summed":
        for layer in layers:
            layer.mlp.config = dataclasses.replace(layer.mlp.config,
                                                   num_shared_experts=1)
    elif fault == "sequential_block":
        def forward(self, x, cache=None, pos=None):
            h = x + self.self_attn(self.input_layernorm(x), cache=cache,
                                   pos=pos)
            return h + self.mlp(self.input_layernorm(h))
        monkeypatch.setattr(window_moe.ParallelWindowMoELayer, "forward",
                            forward)
    elif fault == "rms_for_layer_norm":
        from paddle_tpu.nn import functional as F
        from paddle_tpu.nn.norm import LayerNorm

        monkeypatch.setattr(
            LayerNorm, "forward",
            lambda self, x: F.rms_norm(x, self.weight, self.epsilon))
    elif fault == "stale_ring":
        # a row shorter than the window reads the whole ring: what the
        # slot's last owner left is in it
        real = wa.ring_decode_attention
        monkeypatch.setattr(
            wa, "ring_decode_attention",
            lambda q, k, v, slots, pos, use_pallas=None: real(
                q, k, v, slots, jnp.maximum(pos, k.shape[1] - 1),
                use_pallas))
    eng = make_engine(broken)
    serve(eng, prompt_of(20, seed=40), 2)
    rows = capture(eng)
    short = fault == "stale_ring"
    prompt = prompt_of(3 if short else 21, seed=41)
    req = serve(eng, prompt, 12)
    res = check(ref, builder, period, rows, req, prompt, 12, PERIOD)
    assert res["rows"] == 13
    assert res["ok"] == (fault == "none"), (fault, res)
