"""A residual path of several streams mixed by manifold-constrained
hyper-connections (``ops/hyper_connections.py``, ``models/hc_moe_mla.py``)
around latent attention with YaRN-scaled RoPE and routed experts, on the
serving path: the Sinkhorn step, YaRN's tables, the engine's programs
against the plain reference (``benchmarks/reference/hc_moe_mla_decoder.py``),
the faults the comparison must catch, the health integers, and that a
configuration WITHOUT the hooks runs the parent's program.  float32 on the
CPU, tiny widths."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import harness
from paddle_tpu.ops.hyper_connections import Health

YARN = {"type": "yarn", "factor": 8.0, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 32}
TINY = dict(vocab_size=320, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=48, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=256, rms_norm_eps=1e-6,
            rope_theta=10000.0, rope_scaling=YARN,
            tie_word_embeddings=False, q_lora_rank=32, kv_lora_rank=24,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=20,
            n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
            routed_scaling_factor=2.0, norm_topk_prob=True,
            # 4 rounds in the model-level cases (the step programs compile
            # in a quarter of the time); the published 20 in the cases of
            # the Sinkhorn step itself
            first_k_dense_replace=1, hc_mult=4, hc_sinkhorn_iters=4,
            hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
            check={"margin_eps": 1e-5, "max_left_out_share": 0.002})
ATOL, RMS_REL = 1e-4, 1e-4      # float32 against float32: rounding only
XING = harness.load_json(harness.HERE, "configs", "xing4.0-29b-a4b.json")


@pytest.fixture(scope="module")
def builder():
    return harness.load_module("models", "hc_moe_mla")


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("reference", "hc_moe_mla_decoder")


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


def forward(model, ids):
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    with paddle.no_grad():
        out = model(Tensor(jnp.asarray([ids])))._value[0]
    model.pop_expert_load()
    model.pop_hc_health()
    return np.asarray(out)


# --- the Sinkhorn step -----------------------------------------------------------

def sums(m):
    return (np.abs(np.asarray(m.sum(0)) - 1).max(),
            np.abs(np.asarray(m.sum(1)) - 1).max())


def test_h_res_is_doubly_stochastic_after_20_rounds_and_not_after_2():
    from paddle_tpu.ops import hyper_connections as hc

    rng = np.random.default_rng(0)
    raw = jnp.asarray(rng.normal(size=(4, 4, 50)), jnp.float32)
    done = hc.sinkhorn(jnp.exp(raw), 20, 1e-6)
    assert max(sums(done)) < 1e-5
    early = hc.sinkhorn(jnp.exp(raw), 2, 1e-6)
    assert sums(early)[0] > 1e-3            # columns drift once rows are set
    assert sums(early)[1] < 1e-5            # the last step normalises rows
    # a matrix near a permutation converges slowly (the rate is the limit's
    # second singular value squared): 20 rounds leave what the health
    # integer ``hc_sinkhorn_residual_ppb`` then reports
    near = raw + 4.0 * jnp.eye(4)[:, :, None]
    assert 1e-5 < sums(hc.sinkhorn(jnp.exp(near), 20, 1e-6))[0] < 0.1
    # rows before columns reaches the same matrix (the limit is unique):
    # after 20 rounds the order is within rounding, after 1 it is not
    def rows_first(m, iters):
        for _ in range(iters):
            m = m / (m.sum(1, keepdims=True) + 1e-6)
            m = m / (m.sum(0, keepdims=True) + 1e-6)
        return m

    assert np.abs(rows_first(jnp.exp(raw), 20) - done).max() < 1e-4
    assert np.abs(rows_first(jnp.exp(raw), 1)
                  - hc.sinkhorn(jnp.exp(raw), 1, 1e-6)).max() > 1e-2


def test_coefficients_against_the_reference_and_their_health(ref):
    from paddle_tpu.ops import hyper_connections as hc

    rng = np.random.default_rng(1)
    n, c, t = 4, 16, 9
    x = jnp.asarray(rng.normal(size=(t, n * c)), jnp.float32)
    w = {"phi": jnp.asarray(rng.normal(size=(n * c, 24)) * 0.3, jnp.float32),
         "offsets": jnp.asarray(rng.normal(size=(24,)), jnp.float32),
         "gains": jnp.asarray([0.5, -0.4, 30.0], jnp.float32)}
    co = hc.coefficients(x, w["phi"], w["offsets"], w["gains"], n, 20, 1e-6,
                         1e-6)
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = ref.hyper_coefficients(
            x.reshape(t, n, c), w, n, 20, 1e-6, 1e-6, -30.0, 30.0)
    np.testing.assert_allclose(co.h_pre.T, h_pre, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(co.h_post.T, h_post, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jnp.moveaxis(co.h_res, -1, 0), h_res,
                               rtol=1e-4, atol=1e-6)
    # a gain of 30 drives entries into the clamp: they are counted
    raw = 30.0 * (np.asarray(x) / np.sqrt((np.asarray(x) ** 2).mean(-1) + 1e-6
                                          )[:, None]) @ np.asarray(w["phi"])
    raw = raw[:, 8:] + np.asarray(w["offsets"])[8:]
    assert int(co.clamped) == int((np.abs(raw) >= 30).sum()) > 0
    assert float(co.residual) == pytest.approx(
        np.abs(np.asarray(co.h_res.sum(0)) - 1).max())
    # the two halves of the mix, against einsums
    y = jnp.asarray(rng.normal(size=(t, c)), jnp.float32)
    xs = np.asarray(x).reshape(t, n, c)
    np.testing.assert_allclose(
        hc.mix_in(x, co.h_pre), np.einsum("ti,tic->tc", h_pre, xs),
        rtol=1e-5, atol=1e-6)
    want = np.einsum("tij,tjc->tic", h_res, xs) \
        + np.asarray(h_post)[:, :, None] * np.asarray(y)[:, None, :]
    np.testing.assert_allclose(hc.mix_out(x, co.h_res, co.h_post, y),
                               want.reshape(t, n * c), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(hc.collapse(hc.expand(y, n), n), 4 * y)


# --- YaRN ------------------------------------------------------------------------------

def test_yarn_blends_between_10_and_23_at_the_published_sizes(ref):
    from paddle_tpu.models import moe_mla

    rs = XING["rope_scaling"]
    assert moe_mla.yarn_range(64, 10000.0, 4096, 32, 1) == (10, 23)
    assert moe_mla.yarn_mscale(64, 1) ** 2 == pytest.approx(2.00474, abs=1e-5)
    inv, rope_factor, scale_factor = ref.yarn_inv_freq(XING)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11])
    np.testing.assert_allclose(inv[23:], plain[23:] / rs["factor"])
    assert (inv[11:23] < plain[11:23]).all() \
        and (inv[11:23] > plain[11:23] / 64).all()
    assert rope_factor == 1.0 and scale_factor == pytest.approx(2.00474, abs=1e-5)


def test_the_latent_tables_are_yarns_and_a_factor_of_1_is_plain_rope(ref):
    from paddle_tpu.models import HCMoEMLAConfig, MoEMLAConfig, moe_mla
    from paddle_tpu.models.llama import _rope_tables

    cfg = HCMoEMLAConfig.tiny()
    cos, sin = moe_mla.latent_rope_tables(cfg)
    inv, _, _ = ref.yarn_inv_freq(dict(TINY, rope_scaling=cfg.rope_scaling))
    ang = np.outer(np.arange(256), inv)
    np.testing.assert_allclose(cos, np.cos(ang), atol=2e-5)
    np.testing.assert_allclose(sin, np.sin(ang), atol=2e-5)
    assert moe_mla.softmax_scale(cfg) == pytest.approx(
        (0.1 * np.log(8.0) + 1) ** 2 / np.sqrt(24))
    plain = _rope_tables(8, 256, 10000.0)
    one = HCMoEMLAConfig.tiny(rope_scaling=dict(YARN, factor=1.0))
    for got, want in zip(moe_mla.latent_rope_tables(one), plain):
        np.testing.assert_array_equal(got, want)
    assert moe_mla.softmax_scale(one) == 1.0 / np.sqrt(24)
    # no rope_scaling: the parent's tables and scale, bit for bit
    for c in (MoEMLAConfig.tiny(), HCMoEMLAConfig.tiny(rope_scaling=None)):
        for got, want in zip(moe_mla.latent_rope_tables(c), plain):
            np.testing.assert_array_equal(got, want)
        assert moe_mla.softmax_scale(c) == 1.0 / np.sqrt(24)
    with pytest.raises(ValueError, match="linear"):
        moe_mla.latent_rope_tables(HCMoEMLAConfig.tiny(
            rope_scaling={"type": "linear", "factor": 2.0}))


# --- a configuration without the hooks runs the parent's program -----------------------

def parents_decoder_forward(self, input_ids, pp_microbatches=None,
                            caches=None, pos=None):
    """``LlamaModel.forward`` as the parent commit (8a4aae5) had it on the
    paths a latent model takes: the hooks REMOVED, not looked for."""
    with jax.named_scope("embed"):
        h = self.embed_tokens(input_ids)
    if caches is not None:
        for layer, cache in zip(self.layers, caches):
            h = layer(h, cache=cache, pos=pos)
    else:
        for layer in self.layers:
            h = layer(h)
    return self.norm(h)


def test_the_single_stream_latent_model_traces_to_the_parents_program(
        monkeypatch):
    """Two traces made in THIS process, whatever earlier tests left in it
    (the text of a jaxpr is no constant of the program: a digest recorded
    in one process failed in the driver's six-worker run and passed
    alone): the model as it is, its configuration bringing no hooks,
    against the same model under the parent's forward."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import LlamaForCausalLM, MoEMLAConfig

    paddle.seed(5)
    m = LlamaForCausalLM(MoEMLAConfig.tiny())
    m.eval()
    params = list(m.parameters())
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 256, (1, 24)))

    def f(vals, ids):
        saved = [p._value for p in params]
        for p, v in zip(params, vals):
            p._value = v
        try:
            with paddle.no_grad():
                return m(Tensor(ids))._value
        finally:
            for p, v in zip(params, saved):
                p._value = v

    def trace():
        return str(jax.make_jaxpr(f)([p._value for p in params], ids))

    text = trace()
    assert not hasattr(m.config, "enter_residual")      # hooks absent
    with monkeypatch.context() as mp:                   # hooks removed
        mp.setattr(type(m.llama), "forward", parents_decoder_forward)
        assert trace() == text
    assert "mhc" not in text and m.pop_hc_health() is None
    eng = make_engine(m)
    assert not any(isinstance(t, Health) for t in eng._telemetry)
    assert "hc_streams" not in eng._build_ints("decode", 1, ())
    rows = capture(eng)
    serve(eng, list(range(1, 8)), 2)
    # the step programs' ``stats`` keep the parent's two parts
    assert all(isinstance(s, tuple) and len(s) == 2 for _, _, s in rows)
    assert "serving_hc_" not in eng.metrics.registry.prometheus_text()


# --- the program against the reference ----------------------------------------------------

def check(ref, builder, model, rows, req, prompt, steps):
    got = np.stack([l if l.ndim == 1 else l[0] for _, l, _ in rows])
    ids = prompt + [int(t) for t in req.output_tokens[:steps]]
    full = ref.reference_logits(builder.reference_weights(model), TINY, ids)
    return ref.compare(got, full[len(prompt) - 1:], ATOL, RMS_REL)


def test_the_cacheless_forward_agrees_with_the_reference(ref, builder, model):
    ids = prompt_of(60)
    got = forward(model, ids)
    want = ref.reference_logits(builder.reference_weights(model), TINY, ids)
    res = ref.compare(got, want, ATOL, RMS_REL)
    assert res["ok"] and res["rows_compared"] == 60, res
    assert res["max_abs_diff"] < 5e-6
    # the streams are four and they differ: the value between layers is
    # [batch, tokens, 4 * hidden]
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    with paddle.no_grad():
        x = model.config.enter_residual(
            model.llama.embed_tokens(Tensor(jnp.asarray([ids]))))
        assert x.shape == [1, 60, 256]
        x = model.llama.layers[0](x)._value.reshape(60, 4, 64)
    model.pop_hc_health()
    assert np.abs(np.asarray(x[:, 0] - x[:, 1])).max() > 1e-3


def test_prefill_then_decode_through_the_pages(ref, builder, model):
    eng = make_engine(model)
    assert [p.shape for p in eng._k_pools] == [(64, 4, 128)] * 2   # whole lane tiles
    rows = capture(eng)
    prompt = prompt_of(37)
    req = serve(eng, prompt, 6)
    assert [p for p, _, _ in rows] == ["prefill"] + ["decode"] * 6
    res = check(ref, builder, model, rows, req, prompt, 6)
    assert res["ok"] and res["rows_compared"] == 7, res
    assert res["max_abs_diff"] < 5e-6


def test_a_chunked_prompt_agrees_with_the_reference(ref, builder, model):
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


@pytest.mark.parametrize("kw,word", [
    (dict(unified_step=True), "unified_step"),
    (dict(burst_steps=4), "burst_steps"),
    (dict(role="prefill"), "KV hand-off"),
    (dict(use_pallas_paged=True), "use_pallas_paged"),
])
def test_the_latent_refusals_hold_for_this_kind_too(model, kw, word):
    with pytest.raises(ValueError, match="latent KV cache") as e:
        make_engine(model, **kw)
    assert word in str(e.value)


# --- the faults the comparison must catch ---------------------------------------------------

def one_row_normalisation(m, iters, eps):
    return m / (m.sum(1, keepdims=True) + eps)


def first_stream(x, n):
    return x[..., : x.shape[-1] // n]


@pytest.fixture(scope="module")
def wanted(ref, builder, model):
    ids = prompt_of(48, seed=3)
    return ids, ref.reference_logits(builder.reference_weights(model), TINY,
                                     ids)


def plant(fault, model, monkeypatch):
    """One fault in the PROGRAM, on the shared model, undone by the
    fixture: the reference keeps computing the model as stated."""
    from paddle_tpu.models import moe_mla
    from paddle_tpu.models.llama import _rope_tables
    from paddle_tpu.ops import hyper_connections as hc

    if fault == "no-sinkhorn":
        monkeypatch.setattr(hc, "sinkhorn", one_row_normalisation)
    elif fault == "wrong-collapse":
        monkeypatch.setattr(hc, "collapse", first_stream)
    elif fault == "mean-collapse":
        monkeypatch.setattr(hc, "collapse", lambda x, n: (
            sum(hc._streams(x, n)) / n).astype(x.dtype))
    elif fault == "no-dynamic-term":
        for name, p in model.named_parameters():
            if name.endswith("_hc.gains"):
                monkeypatch.setattr(p, "_value", jnp.zeros_like(p._value))
    for layer in model.llama.layers:
        att = layer.self_attn
        if fault == "unscaled-softmax":
            monkeypatch.setattr(att, "_scale", 1.0 / np.sqrt(24))
        elif fault == "plain-rope":
            cos, sin = _rope_tables(8, 256, 10000.0)
            monkeypatch.setattr(att, "_rope_cos", cos)
            monkeypatch.setattr(att, "_rope_sin", sin)
    assert moe_mla.softmax_scale(model.config) != 1.0 / np.sqrt(24)


@pytest.mark.parametrize("fault", ["no-sinkhorn", "no-dynamic-term",
                                   "wrong-collapse", "unscaled-softmax",
                                   "plain-rope"])
def test_a_planted_fault_fails_the_comparison(fault, ref, model, wanted,
                                              monkeypatch):
    ids, want = wanted
    plant(fault, model, monkeypatch)
    bad = ref.compare(forward(model, ids), want, ATOL, RMS_REL)
    assert not bad["ok"] and bad["max_abs_diff"] > 10 * ATOL, (fault, bad)


def test_a_mean_at_the_collapse_shows_only_through_the_norms_eps(
        ref, model, wanted, monkeypatch):
    """The final norm is free of scale, so a mean where the sum is differs
    by the norm's eps alone: the comparison cannot and need not tell it (the
    wrong collapse it does tell takes one stream)."""
    ids, want = wanted
    assert ref.compare(forward(model, ids), want, ATOL, RMS_REL)["ok"]
    plant("mean-collapse", model, monkeypatch)
    got = forward(model, ids)
    assert 0 < np.abs(got - want).max() < 1e-2 * np.abs(want).max()


# --- the health of the Sinkhorn step rides the launch ------------------------------------------

def test_stats_carry_the_health_and_the_phases_and_metrics_count_it(model):
    eng = make_engine(model)
    (health,) = (t for t in eng._telemetry if isinstance(t, Health))
    assert health.build_ints(eng._view, 1, ()) == {"hc_streams": 4}
    rows = capture(eng)
    seen = {"engine.build": [], "engine.fetch": []}
    real = eng.tracer.phase

    def phase(name, recorder=None, **ints):
        if name in seen:
            seen[name].append(ints)
        return real(name, recorder, **ints)

    eng.tracer.phase = phase
    serve(eng, prompt_of(10, seed=2), 3)
    _, _, stats = rows[0]
    assert isinstance(stats, tuple) and len(stats) == 3
    assert stats[1].shape == (1, 8) and stats[2].shape == (3,)
    clamped, entries, residual = (float(v) for v in stats[2])
    # a bucket of 16 tokens x 16 entries x 4 sublayers
    assert (clamped, entries) == (0.0, 16 * 16 * 4) and 0 <= residual < 0.2
    assert all(b["hc_streams"] == 4 for b in seen["engine.build"])
    fetches = seen["engine.fetch"]
    assert len(fetches) == 4
    assert fetches[0]["hc_entries"] == 16 * 16 * 4
    assert all(f["hc_entries"] == 1 * 16 * 4 for f in fetches[1:])
    assert all(f["hc_res_clamped"] == 0
               and 0 <= f["hc_sinkhorn_residual_ppb"] < 200_000_000
               and "moe_assignments" in f for f in fetches)
    text = eng.metrics.registry.prometheus_text()
    assert "serving_hc_res_clamped_total" in text
    assert "serving_hc_sinkhorn_residual" in text
    assert health.fetch_ints("decode", None) == {}
    ints = health.fetch_ints("decode",
                             np.array([7.0, 96.0, 2.5e-6], np.float32))
    assert ints == {"hc_res_clamped": 7, "hc_entries": 96,
                    "hc_sinkhorn_residual_ppb": 2500}
    assert all(l.attn_hc.health is None and l.mlp_hc.health is None
               for l in model.llama.layers)
