"""The gated delta-rule layer kind through ``EngineCore`` (ISSUE 49): what
the engine allocates from the declarations of a model with a latent page
pool AND slot pools, what its launches carry, rows of mixed lengths, a slot
reused after a longer sequence, and the refusals of both kinds of cache
(their union); preemption by recompute and the benchmark's check are in
``..._gated_delta_engine_check.py``.  ``serving/engine.py`` knows nothing
of this kind: every test here runs on what the layers declare.  float32 on
the CPU at toy widths (``gdn_common.py``)."""

import numpy as np
import pytest

import jax.numpy as jnp

from gdn_common import (ATOL, RMS_REL, TINY, builder, capture,
                        chunks_of_eight, make_engine, model, prompt_of, ref,
                        serve, served_logits)     # noqa: F401  (fixtures)


def test_the_engine_allocates_what_the_layers_declare(model):
    from paddle_tpu.ops.paged_attention import PagedCache, latent_pool_shape
    from paddle_tpu.ops.selective_scan import StateCache, state_step_path

    specs = model.cache_specs()
    assert [s.cache for s in specs] == [StateCache] * 3 + [PagedCache,
                                                          StateCache]
    assert [s.kind for s in specs] == ["kv"] * 3 + ["latent", "kv"]
    assert specs[0].state == (((4, 16, 16), "float32"), ((3 * 128,), None))
    assert specs[3].k == (1, 32) and specs[3].v is None
    eng = make_engine(model)
    assert eng.state_slots == 4 and eng.kv.state_slots == 4
    latent = latent_pool_shape(64, 16, (1, 32))
    assert [p.shape for p in eng._k_pools] == \
        [(5, 4, 16, 16)] * 3 + [latent, (5, 4, 16, 16)]
    assert [p.shape for p in eng._v_pools] == \
        [(5, 384)] * 3 + [(0,), (5, 384)]
    # the recurrent state is float32 whatever the pool's type (logits
    # cannot see a bf16 state on the chip: the cell's check compares the
    # slot itself, ``..._engine_check.py``); the conv window takes the pool's
    half = make_engine(model, dtype=jnp.bfloat16)
    assert half._k_pools[0].dtype == jnp.float32
    assert half._v_pools[0].dtype == half._k_pools[3].dtype == jnp.bfloat16
    # heads of 16 x 16 are no whole float32 tiles: the step is gathered
    # unless the in-place kernel is forced (``test_pallas_gated_delta.py``)
    assert state_step_path((5, 4, 16, 16), None) == "xla"
    assert state_step_path((5, 4, 16, 16), True) == "pallas"
    assert state_step_path((5, 4, 16, 16), True, decode=False) == "xla"
    text = eng.metrics.registry.prometheus_text()
    assert "serving_kv_bytes_per_token 128" in text       # one layer x 32 x 4 B
    assert "serving_state_slots_capacity 4" in text
    # 4 layers x (4 x 16 x 16 + 384) x 4 B
    assert "serving_state_bytes_per_sequence 22528" in text
    assert "serving_moe_held_pair_share" in text


def test_a_launch_carries_the_slots_and_the_held_experts_load(model):
    """The integers of ``engine.build`` and ``engine.fetch``: what
    ``StateSlots`` and ``ExpertLoad`` bring, each named by the layers that
    have it (four delta-rule layers, four expert layers, not the same
    four), and nothing of this kind's own."""
    eng = make_engine(model)
    seen, real = {"engine.build": [], "engine.fetch": []}, eng.tracer.phase

    def phase(name, recorder=None, **ints):
        if name in seen:
            seen[name].append(ints)
        return real(name, recorder, **ints)

    eng.tracer.phase = phase
    serve(eng, prompt_of(21, 3), 3)
    eng.tracer.phase = real
    assert set(seen["engine.build"][0]) == {"state_rows", "state_slots_held"}
    assert all(set(b) == {"rows", "state_rows", "state_slots_held"}
               and b["state_rows"] == 1 for b in seen["engine.build"][1:])
    assert all(set(f) == {"bytes", "moe_assignments", "moe_decode",
                          "moe_streamed", "moe_experts_touched",
                          "moe_max_load",
                          "moe_pairs_held", "moe_held_touched"}
               for f in seen["engine.fetch"])
    slots, load = eng._telemetry
    assert [type(t).__name__ for t in eng._telemetry] == ["StateSlots",
                                                          "ExpertLoad"]
    assert len(slots.layers) == 4 and len(load.layers) == 4
    assert slots.layers != load.layers
    assert list(load.held) == TINY["experts_held"]
    fetched = load.fetch_ints("decode", np.ones((4, 8), np.int32))
    assert fetched["moe_pairs_held"] == 12 and fetched["moe_held_touched"] == 12


def test_rows_of_mixed_lengths_each_agree_with_the_reference(model, builder,
                                                             ref):
    """Three rows admitted together, prompts of 9, 33 and 50 tokens (three
    prefill buckets), decoded side by side: each row's tokens are those of
    the same request served alone, and its logits the reference's."""
    from paddle_tpu.serving.request import SamplingParams

    steps = 7
    prompts = [prompt_of(n, 40 + n) for n in (9, 33, 50)]
    alone = [serve(make_engine(model), p, steps).output_tokens
             for p in prompts]
    eng = make_engine(model)
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=steps + 1,
                                              temperature=0.0))
            for p in prompts]
    for _ in range(100):
        if all(r.finished for r in reqs):
            break
        eng.step()
    assert [r.output_tokens for r in reqs] == alone
    assert eng.kv.state_slots_held == 0
    # one of them against the reference, logits
    eng = make_engine(model)
    rows = capture(eng)
    req = serve(eng, prompts[1], steps)
    ids = prompts[1] + [int(t) for t in req.output_tokens[:steps]]
    want = np.asarray(ref.reference_logits(builder.reference_weights(model),
                                           TINY, ids))[32:]
    res = ref.compare(served_logits(rows, steps), want, ATOL, RMS_REL,
                      margin_eps=0.0, max_left_out_share=0.0)
    assert res["ok"], res


def test_a_slot_reused_after_a_longer_sequence_reads_nothing_left(model):
    eng = make_engine(model)
    first = serve(eng, prompt_of(60, seed=5), 9)
    slot = eng.kv._free_slots[-1]               # the one handed out next
    assert float(jnp.abs(eng._k_pools[0][slot]).max()) > 0    # left dirty
    assert float(jnp.abs(eng._v_pools[0][slot]).max()) > 0
    prompt = prompt_of(23, seed=6)
    again = serve(eng, prompt, 9)
    fresh = serve(make_engine(model), prompt, 9)
    assert again.output_tokens == fresh.output_tokens
    assert first.output_tokens != again.output_tokens


# --- the refusals of a model with a latent pool AND slots: their union ---------------

@pytest.mark.parametrize("kw,why,word", [
    (dict(prefix_cache=True), "per-sequence recurrent state", "prefix_cache"),
    (dict(unified_step=True), "per-sequence recurrent state", "unified_step"),
    (dict(burst_steps=4), "per-sequence recurrent state", "burst_steps"),
    (dict(role="prefill"), "per-sequence recurrent state", "KV hand-off"),
    (dict(aot_path="/nowhere"), "per-sequence recurrent state", "aot"),
    # what the slots' refusals let through, the latent pool's catch
    (dict(use_pallas_paged=True), "latent KV cache", "use_pallas_paged"),
])
def test_paths_without_a_form_for_either_cache_refuse_by_name(model, kw, why,
                                                              word):
    with pytest.raises(ValueError, match=why) as e:
        make_engine(model, **kw)
    assert word in str(e.value)


def test_the_mixer_refuses_a_paged_cache(model):
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.paged_attention import PagedCache

    x = Tensor(jnp.zeros((1, 1, 64), jnp.float32))
    cache = PagedCache.over(jnp.zeros((4, 16, 1, 8)), jnp.zeros((4, 16, 1, 8)))
    with paddle.no_grad(), pytest.raises(TypeError, match="StateCache"):
        model.llama.layers[0].delta(x, cache=cache)
