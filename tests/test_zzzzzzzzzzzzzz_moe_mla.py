"""Latent attention (MLA) and dropless routed experts on the serving path:
the model's cache declaration, the engine's programs against the plain
reference (``benchmarks/reference/moe_mla_decoder.py``), the absorbed
against the expanded path, token identity, and the faults the comparison
must catch.  float32 on the CPU, tiny widths."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import harness

TINY = dict(vocab_size=320, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=48, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=256, rms_norm_eps=1e-5,
            rope_theta=10000.0, tie_word_embeddings=False, q_lora_rank=32,
            kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=20, n_routed_experts=8, n_shared_experts=1,
            num_experts_per_tok=2, routed_scaling_factor=1.8,
            norm_topk_prob=True, first_k_dense_replace=1,
            check={"margin_eps": 1e-5, "max_left_out_share": 0.002})
ATOL, RMS_REL = 1e-4, 1e-4      # float32 against float32: rounding only


@pytest.fixture(scope="module")
def builder():
    return harness.load_module("models", "glm_moe_mla")


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("reference", "moe_mla_decoder")


@pytest.fixture(scope="module")
def model(builder):
    return builder.build(TINY, 7, dtype="float32")


def make_engine(model, **kw):
    from paddle_tpu.serving import EngineConfig, EngineCore, SchedulerConfig

    sched = kw.pop("scheduler", None) or SchedulerConfig(max_num_seqs=8)
    cfg = dict(num_blocks=64, block_size=4, dtype=jnp.float32,
               prefix_cache=False, scheduler=sched)
    cfg.update(kw)
    return EngineCore(model, config=EngineConfig(**cfg))


def capture(engine):
    """Every launch's program name and logits, from outside (as the
    benchmark's probe takes them)."""
    rows, orig = [], engine._step_call

    def call(program, bucket, fn, *args):
        out = orig(program, bucket, fn, *args)
        rows.append((program, np.asarray(out[1], np.float32), out[2]))
        return out

    engine._step_call = call
    return rows


def serve(engine, prompt, steps):
    from paddle_tpu.serving.request import SamplingParams

    req = engine.add_request(prompt, SamplingParams(
        max_new_tokens=steps + 1, temperature=0.0))
    for _ in range(steps + 40):
        if req.finished:
            break
        engine.step()
    assert req.finished
    return req


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"],
                                                n).tolist()


# --- the cache declaration -----------------------------------------------------

def test_layers_declare_a_latent_row_and_no_values(model):
    from paddle_tpu.ops.paged_attention import CacheSpec

    specs = model.cache_specs()
    assert specs == [CacheSpec(k=(1, 32), v=None, kind="latent")] * 3
    assert specs[0].values_per_token() == 24 + 8


def test_dense_layers_declare_keys_and_values():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.ops.paged_attention import CacheSpec

    m = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    assert m.cache_specs() == [CacheSpec(k=(2, 16), v=(2, 16))] * 2
    eng = make_engine(m)
    assert [p.shape for p in eng._k_pools] == [(64, 4, 2, 16)] * 2
    assert [p.shape for p in eng._v_pools] == [(64, 4, 2, 16)] * 2
    serve(eng, prompt_of(6), 1)
    assert eng._telemetry == []           # no routing series for a dense model
    assert "serving_moe_" not in eng.metrics.registry.prometheus_text()


def test_engine_allocates_by_the_declaration(model):
    eng = make_engine(model)
    # a token's 32 values in whole lane tiles: the array the TPU lays
    # row-major, a page contiguous (``latent_pool_shape``, PR 46)
    assert [p.shape for p in eng._k_pools] == [(64, 4, 128)] * 3
    assert [p.size for p in eng._v_pools] == [0, 0, 0]
    text = eng.metrics.registry.prometheus_text()
    assert "serving_kv_bytes_per_token 384" in text     # 32 values x 4 B x 3


@pytest.mark.parametrize("kw,word", [
    (dict(unified_step=True), "unified_step"),
    (dict(burst_steps=4), "burst_steps"),
    (dict(role="prefill"), "KV hand-off"),
    (dict(role="decode"), "KV hand-off"),
    (dict(use_pallas_paged=True), "use_pallas_paged"),
])
def test_paths_without_a_latent_form_refuse_by_name(model, kw, word):
    with pytest.raises(ValueError, match="latent KV cache") as e:
        make_engine(model, **kw)
    assert word in str(e.value)


def test_speculative_verify_refuses_by_name(model):
    from paddle_tpu.serving import SchedulerConfig
    from paddle_tpu.serving.spec import SpecConfig

    with pytest.raises(ValueError, match="latent KV cache") as e:
        make_engine(model, spec=SpecConfig(), unified_step=True,
                    scheduler=SchedulerConfig(max_num_seqs=8,
                                              max_tokens_per_step=64))
    assert "spec (speculative verify)" in str(e.value)


def test_handoff_refuses_by_name(model):
    from paddle_tpu.serving import handoff

    with pytest.raises(handoff.HandoffError, match="latent cache"):
        handoff.pool_meta(make_engine(model))


# --- the engine's programs against the reference -------------------------------

def check(ref, builder, model, rows, req, prompt, steps):
    got = np.stack([l if l.ndim == 1 else l[0] for _, l, _ in rows])
    ids = prompt + [int(t) for t in req.output_tokens[:steps]]
    full = np.asarray(ref.reference_logits(
        builder.reference_weights(model), TINY, ids))
    return ref.compare(got, full[len(prompt) - 1:], ATOL, RMS_REL)


def test_prefill_then_decode_through_the_pages(ref, builder, model):
    eng = make_engine(model)
    rows = capture(eng)
    prompt = prompt_of(37)
    req = serve(eng, prompt, 6)
    assert [p for p, _, _ in rows] == ["prefill"] + ["decode"] * 6
    res = check(ref, builder, model, rows, req, prompt, 6)
    assert res["ok"] and res["rows_compared"] == 7, res
    assert res["max_abs_diff"] < 2e-6


def test_chunked_prefill_and_resume(ref, builder, model):
    from paddle_tpu.serving import SchedulerConfig

    eng = make_engine(model, prefix_cache=True, scheduler=SchedulerConfig(
        max_num_seqs=8, max_prefill_tokens_per_step=16))
    rows = capture(eng)
    prompt = prompt_of(45, seed=1)
    req = serve(eng, prompt, 4)
    programs = [p for p, _, _ in rows]
    assert programs.count("chunk") >= 2 and programs[-4:] == ["decode"] * 4
    last_chunk = max(i for i, p in enumerate(programs) if p == "chunk")
    res = check(ref, builder, model, rows[last_chunk:], req, prompt, 4)
    assert res["ok"] and res["rows_compared"] == 5, res
    # a second request with the same prompt resumes from the cached blocks
    rows.clear()
    again = serve(eng, prompt, 4)
    assert again.output_tokens == req.output_tokens
    assert "prefill" not in [p for p, _, _ in rows]


def test_preemption_by_recompute_gives_the_same_tokens(model):
    from paddle_tpu.serving.request import SamplingParams

    calm = make_engine(model)
    prompts = [prompt_of(14, seed=s) for s in range(4)]
    want = [serve(calm, p, 12).output_tokens for p in prompts]
    tight = make_engine(model, num_blocks=18)       # 17 x 4 = 68 tokens
    reqs = [tight.add_request(p, SamplingParams(max_new_tokens=13,
                                                temperature=0.0))
            for p in prompts]
    for _ in range(400):
        if all(r.finished for r in reqs):
            break
        tight.step()
    reg, labels = tight.metrics.registry, tight.metrics.labels
    assert reg.counter("serving_preemptions_total", **labels).value > 0
    assert [r.output_tokens for r in reqs] == want


def test_absorbed_decode_agrees_with_the_expanded_path():
    from paddle_tpu.ops.paged_attention import (
        latent_expanded_attention, latent_paged_decode_attention,
        latent_paged_prefill_attention)

    rng = np.random.default_rng(3)
    heads, nope, rope, v, rank, bs = 4, 16, 8, 20, 24, 4
    pool = jnp.asarray(rng.normal(size=(12, bs, 1, rank + rope)), jnp.float32)
    w = (jnp.asarray(rng.normal(size=(heads, rank, nope)), jnp.float32),
         jnp.asarray(rng.normal(size=(heads, rank, v)), jnp.float32))
    tables = jnp.asarray([[3, 7, 1], [5, 2, 0]], jnp.int32)
    lens = jnp.asarray([11, 6], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, heads, nope + rope)), jnp.float32)
    got = latent_paged_decode_attention(q, pool, w, tables, lens, rank, 0.2)
    lat = pool[tables].reshape(2, 3 * bs, rank + rope)
    want = latent_expanded_attention(q[:, None], lat, w, rank, 0.2,
                                     q_start=lens - 1, lens=lens)[:, 0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the paged prefill is the expanded path over the gathered pages
    chunk = latent_paged_prefill_attention(q[:, None], pool, w, tables, lens,
                                           lens - 1, rank, 0.2)[:, 0]
    np.testing.assert_allclose(chunk, want, rtol=1e-6, atol=1e-6)


def test_decode_rows_split_into_groups_give_the_same(monkeypatch):
    from paddle_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(4)
    pool = jnp.asarray(rng.normal(size=(9, 4, 1, 32)), jnp.float32)
    w = (jnp.asarray(rng.normal(size=(4, 24, 16)), jnp.float32),
         jnp.asarray(rng.normal(size=(4, 24, 20)), jnp.float32))
    tables = jnp.asarray(rng.integers(0, 9, (8, 2)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, 8, (8,)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(8, 4, 24)), jnp.float32)
    whole = pa.latent_paged_decode_attention(q, pool, w, tables, lens, 24, 0.2)
    monkeypatch.setattr(pa, "_LATENT_CONTEXT_BYTES", 8 * 32 * 4 * 2)
    split = pa.latent_paged_decode_attention(q, pool, w, tables, lens, 24, 0.2)
    np.testing.assert_array_equal(whole, split)


# --- routing ---------------------------------------------------------------------

def test_expert_choices_are_the_references_in_float32(ref, builder, model):
    from paddle_tpu.parallel.moe import sigmoid_topk_route

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    w = builder.reference_weights(model)["layers"][1]
    ids, weights = sigmoid_topk_route(x, w["router"], w["router_bias"], 2,
                                      scale=1.8)
    with jax.default_matmul_precision("highest"):
        share, margin = ref.routing(jax.nn.sigmoid(x @ w["router"]),
                                    w["router_bias"], 2, 1.8)
    chosen = np.zeros((64, 8), bool)
    chosen[np.arange(64)[:, None], np.asarray(ids)] = True
    assert (chosen == (np.asarray(share) > 0)).all()
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(share), np.asarray(ids), 1), weights,
        rtol=1e-6)
    assert float(margin.min()) > 0
    # the bias selects and does not weigh: a huge bias on one expert puts
    # it in every token's choice, with a weight made of the SCORES alone
    bias = jnp.zeros(8).at[3].set(10.0)
    ids, weights = sigmoid_topk_route(x, w["router"], bias, 2, scale=1.0)
    assert (np.asarray(ids) == 3).any(axis=1).all()
    np.testing.assert_allclose(np.asarray(weights).sum(1), 1.0, rtol=1e-6)


def per_token_loop(x, ids, weights, w_gate_up, w_down):
    f = w_down.shape[1]
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, wt in zip(ids[t], weights[t]):
            h = x[t] @ w_gate_up[e]
            g, u = h[:f], h[f:]
            out[t] += wt * ((g / (1 + np.exp(-g)) * u) @ w_down[e])
    return out


def test_dropless_experts_under_a_routing_skewed_onto_two():
    from paddle_tpu.parallel.moe import dropless_experts

    rng = np.random.default_rng(6)
    T, H, F, E, k = 40, 16, 12, 8, 2
    x = rng.normal(size=(T, H)).astype(np.float32)
    gu = rng.normal(size=(E, H, 2 * F)).astype(np.float32) * 0.3
    down = rng.normal(size=(E, F, H)).astype(np.float32) * 0.3
    ids = np.tile(np.array([[5, 2]], np.int32), (T, 1))     # all on 2 experts
    ids[7] = [2, 6]
    weights = rng.uniform(0.2, 1.0, (T, k)).astype(np.float32)
    out, load = dropless_experts(jnp.asarray(x), jnp.asarray(ids),
                                 jnp.asarray(weights), jnp.asarray(gu),
                                 jnp.asarray(down), E)
    assert load.tolist() == [0, 0, T, 0, 0, T - 1, 1, 0]    # far past any capacity
    np.testing.assert_allclose(out, per_token_loop(x, ids, weights, gu, down),
                               rtol=2e-4, atol=2e-5)


def test_shares_of_the_experts_add_up_to_the_whole_layer():
    from paddle_tpu.parallel.moe import dropless_experts

    rng = np.random.default_rng(8)
    T, H, F, E, k = 24, 16, 12, 8, 3
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    gu = jnp.asarray(rng.normal(size=(E, H, 2 * F)) * 0.3, jnp.float32)
    down = jnp.asarray(rng.normal(size=(E, F, H)) * 0.3, jnp.float32)
    ids = jnp.asarray(np.stack([rng.permutation(E)[:k] for _ in range(T)]),
                      jnp.int32)
    weights = jnp.asarray(rng.uniform(0.2, 1.0, (T, k)), jnp.float32)
    whole, load = dropless_experts(x, ids, weights, gu, down, E)
    parts = []
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        part, part_load = dropless_experts(
            x, ids, weights, gu[np.array(held)], down[np.array(held)], E, held)
        assert part_load.tolist() == load.tolist()    # the router sees all
        parts.append(part)
    np.testing.assert_allclose(parts[0] + parts[1], whole, rtol=1e-5, atol=1e-6)


def test_a_request_alone_and_in_a_crowd_gives_the_same(model):
    """Token identity through the engine: the same request alone and among
    seven others.  Its prefill is the same one-row program both times, so
    those logits agree bit for bit; the decode rows run in another row
    bucket (XLA's CPU matmul of 1 row and of 8 round differently in the
    last bit), so there the tokens are what is compared."""
    from paddle_tpu.serving.request import SamplingParams

    prompt = prompt_of(21, seed=11)
    alone = make_engine(model, num_blocks=256)
    rows = capture(alone)
    req = serve(alone, prompt, 5)
    want = rows[0][1]

    crowd = make_engine(model, num_blocks=256)
    rows = capture(crowd)
    greedy = SamplingParams(max_new_tokens=6, temperature=0.0)
    for s in range(7):
        crowd.add_request(prompt_of(9 + 3 * s, seed=20 + s), greedy)
    mine = crowd.add_request(prompt, greedy)
    for _ in range(60):
        if mine.finished:
            break
        crowd.step()
    assert mine.output_tokens == req.output_tokens
    assert max(l.shape[0] for p, l, _ in rows if p == "decode") == 8
    prefills = [l for p, l, _ in rows if p == "prefill"]
    assert any((l == want).all() for l in prefills)


def test_a_rows_output_does_not_depend_on_who_shares_its_batch(model):
    """The expert layer alone, where a capacity would bite: token 0 among
    63 tokens that crowd its experts and among 63 others gives the same
    bits (same shapes, so the same matmul kernels), and alone the same
    values to float32 rounding."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    layer = model.llama.layers[1].mlp
    rng = np.random.default_rng(12)
    x0 = rng.normal(size=(1, 1, 64))
    crowd_a = np.concatenate([x0, np.repeat(x0, 63, 1)
                              + 1e-3 * rng.normal(size=(1, 63, 64))], 1)
    crowd_b = np.concatenate([x0, rng.normal(size=(1, 63, 64))], 1)
    with paddle.no_grad():
        a, b, alone = (layer(Tensor(jnp.asarray(v, jnp.float32)))._value
                       for v in (crowd_a, crowd_b, x0))
    layer.load = None
    np.testing.assert_array_equal(a[0, 0], b[0, 0])
    np.testing.assert_allclose(alone[0, 0], a[0, 0], rtol=2e-5, atol=1e-8)


# --- the routing load rides the launch -------------------------------------------

def test_stats_carry_the_load_and_metrics_count_it(model):
    eng = make_engine(model)
    rows = capture(eng)
    serve(eng, prompt_of(10, seed=2), 3)
    program, _, stats = rows[0]
    assert isinstance(stats, tuple) and stats[1].shape == (2, 8)
    assert stats[1].dtype == jnp.int32
    assert int(stats[1].sum()) == 2 * 2 * 16        # layers x k x bucket 16
    text = eng.metrics.registry.prometheus_text()
    for name in ("serving_moe_assignments_total",
                 "serving_moe_experts_touched_total",
                 "serving_moe_load_max_over_mean"):
        assert name in text
    (load,) = eng._telemetry            # parallel.moe.ExpertLoad
    ints = load.fetch_ints("decode", np.array([[3, 1, 0, 0], [0, 2, 2, 0]]))
    assert ints == {"moe_assignments": 8, "moe_experts_touched": 4,
                    "moe_max_load": 5, "moe_decode": 1, "moe_streamed": 0}
    assert load.fetch_ints("prefill", None) == {}


def test_no_tracer_outlives_its_trace(model):
    eng = make_engine(model)
    serve(eng, prompt_of(10, seed=2), 2)
    assert all(getattr(l.mlp, "load", None) is None
               for l in model.llama.layers)


# --- the faults the comparison must catch ----------------------------------------

def bf16_router(real):
    def route(x, w, bias, k, **kw):
        r = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        return real(r(x), r(w), bias, k, **kw)
    return route


def bias_weighs(real):
    def route(x, w, bias, k, scale=1.0, normalize=True):
        ids, _ = real(x, w, bias, k, scale=scale, normalize=normalize)
        s = jax.nn.sigmoid(x @ w) + bias
        wt = jnp.take_along_axis(s, ids, -1)
        return ids, scale * wt / wt.sum(-1, keepdims=True)
    return route


def capacity_drops(real):
    def experts(x, ids, weights, gu, down, n, held=None, limit=None):
        T, k = ids.shape
        cap = max(1, int(1.25 * k * T / n))
        onehot = jax.nn.one_hot(ids.reshape(-1), n, dtype=jnp.int32)
        place = (jnp.cumsum(onehot, 0) * onehot).sum(-1).reshape(T, k)
        return real(x, ids, jnp.where(place <= cap, weights, 0.0), gu, down,
                    n, held, limit)
    return experts


@pytest.mark.parametrize("fault", ["none", "bf16_router", "no_shared_expert",
                                   "no_scaling", "bias_weighs",
                                   "dropped_token"])
def test_planted_fault_fails_the_comparison(ref, builder, fault, monkeypatch):
    """Four sequences of 250 tokens, every position compared (the
    cache-less forward): enough rows that a router rounded to bf16 flips
    some choice, and every other fault moves every row."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import moe_mla

    broken = builder.build(TINY, 7, dtype="float32")
    if fault == "bf16_router":
        monkeypatch.setattr(moe_mla, "sigmoid_topk_route",
                            bf16_router(moe_mla.sigmoid_topk_route))
    elif fault == "bias_weighs":
        monkeypatch.setattr(moe_mla, "sigmoid_topk_route",
                            bias_weighs(moe_mla.sigmoid_topk_route))
    elif fault == "dropped_token":
        monkeypatch.setattr(moe_mla, "dropless_experts",
                            capacity_drops(moe_mla.dropless_experts))
    elif fault == "no_shared_expert":
        for layer in broken.llama.layers[1:]:
            layer.mlp.shared_experts.forward = lambda x: x * 0.0
    elif fault == "no_scaling":
        broken.config.routed_scaling_factor = 1.0
    weights = builder.reference_weights(
        builder.build(TINY, 7, dtype="float32"))
    got, want = [], []
    for seed in range(4):
        ids = prompt_of(250, seed=30 + seed)
        with paddle.no_grad():
            got.append(np.asarray(broken(Tensor(jnp.asarray([ids])))._value[0]))
        want.append(np.asarray(ref.reference_logits(weights, TINY, ids)))
    broken.pop_expert_load()
    res = ref.compare(np.concatenate(got), np.concatenate(want), ATOL, RMS_REL)
    assert res["rows"] == 1000 and res["left_out_share"] <= 0.002, res
    assert res["ok"] == (fault == "none"), {
        k: v for k, v in res.items() if not k.startswith("row_")}
