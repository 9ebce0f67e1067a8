"""Selective-scan (Mamba-1) mixers among attention layers on the serving
path: what the layers declare (per-sequence state beside per-token rows),
the slots the cache manager owns beside the pages, the engine's prefill,
chunk, decode and recompute programs against the plain reference
(``benchmarks/reference/jamba_hybrid_decoder.py``), token identity, the
refusals by name, and the faults the comparison must catch.  float32 on
the CPU, tiny widths, two periods of a shortened layer pattern."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import harness

TINY = dict(vocab_size=320, hidden_size=64, intermediate_size=128,
            num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=1,
            max_position_embeddings=512, rms_norm_eps=1e-6,
            tie_word_embeddings=True, attn_layer_period=3, attn_layer_offset=1,
            mamba_d_state=8, mamba_d_conv=4, mamba_dt_rank=8, mamba_expand=2,
            mamba_conv_bias=True, mamba_proj_bias=False)
ATOL, RMS_REL = 1e-4, 1e-4      # float32 against float32: rounding only


@pytest.fixture(scope="module")
def builder():
    return harness.load_module("models", "jamba_hybrid")


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("reference", "jamba_hybrid_decoder")


@pytest.fixture(scope="module")
def model(builder):
    return builder.build(TINY, 7, dtype="float32")


def make_engine(model, **kw):
    from paddle_tpu.serving import EngineConfig, EngineCore, SchedulerConfig

    sched = kw.pop("scheduler", None) or SchedulerConfig(max_num_seqs=8)
    cfg = dict(num_blocks=128, block_size=4, dtype=jnp.float32,
               prefix_cache=False, scheduler=sched)
    cfg.update(kw)
    return EngineCore(model, config=EngineConfig(**cfg))


def capture(engine):
    """Every launch's program name and logits, from outside (as the
    benchmark's probe takes them)."""
    rows, orig = [], engine._step_call

    def call(program, bucket, fn, *args):
        out = orig(program, bucket, fn, *args)
        rows.append((program, np.asarray(out[1], np.float32)))
        return out

    engine._step_call = call
    return rows


def serve(engine, prompt, steps):
    from paddle_tpu.serving.request import SamplingParams

    req = engine.add_request(prompt, SamplingParams(
        max_new_tokens=steps + 1, temperature=0.0))
    for _ in range(steps + 60):
        if req.finished:
            break
        engine.step()
    assert req.finished
    return req


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"],
                                                n).tolist()


def check(ref, builder, model, rows, req, prompt, steps):
    got = np.stack([l if l.ndim == 1 else l[0] for _, l in rows])
    ids = prompt + [int(t) for t in req.output_tokens[:steps]]
    full = np.asarray(ref.reference_logits(
        builder.reference_weights(model), TINY, ids))
    return ref.compare(got, full[len(prompt) - 1:], ATOL, RMS_REL)


# --- what the layers declare, and what is allocated from it --------------------

def test_layers_declare_state_or_rows_in_the_published_order(model):
    from paddle_tpu.models import MambaDecoderLayer
    from paddle_tpu.ops.paged_attention import CacheSpec

    kinds = [isinstance(l, MambaDecoderLayer) for l in model.llama.layers]
    assert kinds == [True, False, True, True, False, True]
    state = CacheSpec(state=(((8, 128), "float32"), ((3 * 128,), None)))
    rows = CacheSpec(k=(1, 16), v=(1, 16))
    assert model.cache_specs() == [state, rows, state, state, rows, state]
    assert state.values_per_token() == 0 and rows.values_per_token() == 32
    assert state.state_bytes_per_sequence("float32") == (8 * 128 + 384) * 4
    assert state.state_bytes_per_sequence("bfloat16") == 8 * 128 * 4 + 384 * 2
    assert rows.state_bytes_per_sequence("float32") == 0


def test_a_declaration_is_rows_or_state_and_never_nothing():
    from paddle_tpu.ops.paged_attention import CacheSpec

    with pytest.raises(ValueError, match="not both"):
        CacheSpec(k=(1, 8), v=(1, 8), state=(((2, 4), None), ((4,), None)))
    with pytest.raises(ValueError, match="two"):
        CacheSpec(state=(((2, 4), None),))
    with pytest.raises(ValueError, match="keeps nothing"):
        CacheSpec()


def test_engine_allocates_slots_and_pages_by_the_declaration(model):
    eng = make_engine(model)
    assert eng.state_slots == 8 and eng.kv.state_slots == 8
    assert [p.shape for p in eng._k_pools] == [
        (9, 8, 128), (128, 4, 1, 16), (9, 8, 128), (9, 8, 128),
        (128, 4, 1, 16), (9, 8, 128)]
    assert [p.shape for p in eng._v_pools] == [
        (9, 384), (128, 4, 1, 16), (9, 384), (9, 384), (128, 4, 1, 16),
        (9, 384)]
    assert all(p.dtype == jnp.float32 for p in eng._k_pools)
    text = eng.metrics.registry.prometheus_text()
    assert "serving_kv_bytes_per_token 256" in text       # 2 layers x 32 x 4 B
    assert "serving_state_slots_capacity 8" in text
    assert "serving_state_bytes_per_sequence 22528" in text   # 4 x 1408 x 4 B
    assert "serving_state_slots_held 0" in text


def test_a_dense_model_has_no_slots_and_no_slot_series():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    eng = make_engine(LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2)),
                      prefix_cache=True)
    assert eng.state_slots == 0 and eng.kv.state_slots == 0
    assert eng._telemetry == [] and eng._build_ints("decode", 3, ()) == {}
    assert "serving_state" not in eng.metrics.registry.prometheus_text()
    assert eng.kv.num_free == 127 and eng.kv.can_start_sequence()


# --- slots beside pages in one manager -------------------------------------------

def test_a_sequence_takes_its_slot_with_its_first_block():
    from paddle_tpu.serving.kv_manager import KVCacheManager

    kv = KVCacheManager(32, 4, enable_prefix_cache=False, state_slots=4)
    assert (kv.num_free, kv.num_available, kv.state_slots_held) == (31, 27, 0)
    assert kv.allocate("a", 9) and kv.allocate("b", 1)
    assert kv.table("a")[0] == 1 and kv.table("b") == [2]
    assert all(b > 4 for b in kv.table("a")[1:]) and len(kv.table("a")) == 3
    assert kv.table("c") == []                     # no blocks, no slot
    assert kv.state_slots_held == 2 and kv.num_available == 25
    assert kv.occupancy() == pytest.approx(4 / 31)
    kv.commit("a", 9)
    assert kv.append_slot("a") == (kv.table("a")[2], 1)   # no new slot taken
    assert kv.state_slots_held == 2
    # freed together: the slot returns with the last block
    assert kv.free("a") == 3
    assert kv.state_slots_held == 1 and kv.num_available == 27
    assert kv.allocate("c", 2) and kv.table("c") == [1]


def test_exhaustion_of_either_takes_nothing():
    from paddle_tpu.serving.kv_manager import KVCacheManager

    kv = KVCacheManager(12, 4, enable_prefix_cache=False, state_slots=2)
    assert kv.allocate("a", 4) and kv.allocate("b", 4)
    assert not kv.can_start_sequence()
    assert not kv.allocate("c", 1)                 # no slot
    assert not kv.has("c") and kv.num_available == 9
    kv.free("b")
    assert not kv.allocate("c", 4 * 11)            # a slot, too few blocks
    assert kv.state_slots_held == 1 and kv.num_available == 9
    assert kv.allocate("c", 4 * 10) and kv.num_available == 0
    kv.commit("a", 4)
    assert kv.append_slot("a") is None             # pages out: a scheduling event
    with pytest.raises(ValueError, match="cannot be forked"):
        KVCacheManager(12, 4, enable_prefix_cache=True, state_slots=2)
    with pytest.raises(ValueError, match="at least 5 blocks"):
        KVCacheManager(4, 4, enable_prefix_cache=False, state_slots=2)


def test_truncate_to_nothing_returns_the_slot():
    from paddle_tpu.serving.kv_manager import KVCacheManager

    kv = KVCacheManager(16, 4, enable_prefix_cache=False, state_slots=2)
    kv.allocate("a", 9)
    kv.commit("a", 9)
    assert kv.truncate("a", 0) == 3 and kv.state_slots_held == 0
    assert kv.num_free == 15 and sorted(kv._free_slots) == [1, 2]


# --- the refusals, by name -------------------------------------------------------

@pytest.mark.parametrize("kw,word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(unified_step=True), "unified_step"),
    (dict(burst_steps=4), "burst_steps"),
    (dict(role="prefill"), "KV hand-off"),
    (dict(role="decode"), "KV hand-off"),
    (dict(aot_path="/nowhere"), "aot"),
])
def test_paths_without_a_form_for_state_refuse_by_name(model, kw, word):
    with pytest.raises(ValueError, match="per-sequence recurrent state") as e:
        make_engine(model, **kw)
    assert word in str(e.value)


def test_the_default_engine_config_refuses_for_its_prefix_cache(model):
    from paddle_tpu.serving import EngineConfig, EngineCore

    with pytest.raises(ValueError, match="prefix_cache=False"):
        EngineCore(model, config=EngineConfig())


def test_speculative_verify_and_the_audit_refuse_by_name(model):
    from paddle_tpu.observability.audit import AuditConfig
    from paddle_tpu.serving import SchedulerConfig
    from paddle_tpu.serving.spec import SpecConfig

    with pytest.raises(ValueError, match="recurrent state") as e:
        make_engine(model, spec=SpecConfig(), unified_step=True,
                    scheduler=SchedulerConfig(max_num_seqs=8,
                                              max_tokens_per_step=64))
    assert "spec (speculative verify" in str(e.value)
    with pytest.raises(ValueError, match="recurrent state") as e:
        make_engine(model, audit=AuditConfig(enabled=True))
    assert "audit (the shadow re-execution" in str(e.value)


def test_tensor_parallel_refuses_by_name(model, monkeypatch):
    from paddle_tpu.parallel import utils

    monkeypatch.setattr(utils, "axis_size",
                        lambda name: 2 if name == "mp" else 1)
    with pytest.raises(ValueError, match="recurrent state") as e:
        make_engine(model)
    assert "mp=2 (slot pools are not sharded)" in str(e.value)


def test_handoff_refuses_by_name(model):
    from paddle_tpu.serving import handoff

    with pytest.raises(handoff.HandoffError, match="recurrent state"):
        handoff.pool_meta(make_engine(model))


def test_a_mixer_refuses_a_paged_cache(model):
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.paged_attention import PagedCache

    x = Tensor(jnp.zeros((1, 1, 64), jnp.float32))
    with paddle.no_grad(), pytest.raises(TypeError, match="StateCache"):
        model.llama.layers[0].mamba(x, cache=PagedCache(None, None))


# --- the scan ----------------------------------------------------------------------

def scan_inputs(T, D=16, N=4, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    A = -jnp.exp(f(N, D) * 0.3)
    return (f(1, T, D), jax.nn.softplus(f(1, T, D) - 2), A, f(1, T, N),
            f(1, T, N), f(1, N, D))


def test_scan_is_the_step_repeated_and_stops_at_the_last_real_token():
    from paddle_tpu.ops.selective_scan import selective_scan, selective_step

    x, dt, A, Bm, Cm, h0 = scan_inputs(19)
    h, ys = h0, []
    for t in range(13):
        y, h = selective_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], h)
        ys.append(y)
    y_scan, h_scan = selective_scan(x, dt, A, Bm, Cm, h0, jnp.int32(13))
    np.testing.assert_allclose(y_scan[:, :13], jnp.stack(ys, 1), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(h_scan, h, rtol=1e-6, atol=1e-6)
    # the positions past it still give outputs, and change no state
    assert y_scan.shape == (1, 19, 16)
    _, h_all = selective_scan(x, dt, A, Bm, Cm, h0, jnp.int32(19))
    assert float(jnp.abs(h_all - h).max()) > 1e-3


@pytest.mark.parametrize("unroll", [1, 4, 32])
def test_scan_unroll_changes_rounding_only(unroll):
    """How many positions a loop iteration advances is a compile-time
    choice: the compiler may fuse a multiply-add differently, no more."""
    from paddle_tpu.ops.selective_scan import selective_scan

    x, dt, A, Bm, Cm, h0 = scan_inputs(21, seed=3)
    want = selective_scan(x, dt, A, Bm, Cm, h0, jnp.int32(17), unroll=8)
    got = selective_scan(x, dt, A, Bm, Cm, h0, jnp.int32(17), unroll=unroll)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5, atol=2e-6)


def test_one_kv_head_takes_the_gather_path_unless_forced():
    """Multi-query decode (20 query heads on one KV head of 128) goes down
    the XLA gather path by what the wrapper can see; a 4:1 group of the
    same head size keeps the kernel."""
    from paddle_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    bt = jnp.asarray(rng.integers(1, 8, (2, 3)), jnp.int32)
    sl = jnp.asarray([5, 20], jnp.int32)
    q, kc, vc = f(2, 20, 128), f(8, 8, 1, 128), f(8, 8, 1, 128)
    ref = pa.paged_attention(q, kc, vc, bt, sl)
    assert pa.last_path == "xla"
    forced = pa.paged_attention(q, kc, vc, bt, sl, use_pallas=True)
    assert pa.last_path == "pallas"
    np.testing.assert_allclose(forced, ref, atol=2e-5, rtol=1e-4)
    pa.paged_attention(f(2, 8, 128), f(8, 8, 2, 128), f(8, 8, 2, 128), bt, sl)
    assert pa.last_path == "pallas"


def test_conv_window_is_the_inputs_ending_at_the_last_real_token():
    from paddle_tpu.ops.selective_scan import causal_conv, conv_window

    u = jnp.arange(1.0, 11.0).reshape(1, 10, 1)
    w = jnp.asarray([[0.0], [0.0], [0.0], [1.0]])        # identity tap
    x, padded = causal_conv(u, jnp.zeros((1, 3, 1)), w, None)
    np.testing.assert_allclose(x, jax.nn.silu(u))
    assert conv_window(padded, jnp.int32(7), 4)[0, :, 0].tolist() == [5, 6, 7]
    assert conv_window(padded, jnp.int32(2), 4)[0, :, 0].tolist() == [0, 1, 2]
    # a carried window is what came before
    x2, _ = causal_conv(u[:, 7:], padded[:, 7:10], jnp.ones((4, 1)) / 4,
                        jnp.asarray([0.5]))
    np.testing.assert_allclose(
        x2[0, 0, 0], jax.nn.silu((5 + 6 + 7 + 8) / 4 + 0.5), rtol=1e-6)


# --- the engine's programs against the reference ---------------------------------

def test_cache_less_forward_agrees_with_the_reference(ref, builder, model):
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    ids = prompt_of(70, seed=2)
    with paddle.no_grad():
        got = np.asarray(model(Tensor(jnp.asarray([ids])))._value[0])
    want = ref.reference_logits(builder.reference_weights(model), TINY, ids)
    res = ref.compare(got, want, ATOL, RMS_REL)
    assert res["ok"] and res["rows"] == 70, res


def test_prefill_shorter_than_its_bucket_then_decode_through_slots(
        ref, builder, model):
    eng = make_engine(model)
    rows = capture(eng)
    prompt = prompt_of(37)                      # bucket 64: 27 pad positions
    req = serve(eng, prompt, 12)
    assert [p for p, _ in rows] == ["prefill"] + ["decode"] * 12
    res = check(ref, builder, model, rows, req, prompt, 12)
    assert res["ok"] and res["rows"] == 13, res
    assert res["max_abs_diff"] < 5e-6
    assert eng.kv.state_slots_held == 0         # finished: the slot is back


def test_chunked_prefill_agrees_with_one_shot_and_the_reference(
        ref, builder, model):
    from paddle_tpu.serving import SchedulerConfig

    prompt = prompt_of(45, seed=1)
    want = serve(make_engine(model), prompt, 6).output_tokens
    eng = make_engine(model, scheduler=SchedulerConfig(
        max_num_seqs=8, max_prefill_tokens_per_step=16))
    rows = capture(eng)
    req = serve(eng, prompt, 6)
    programs = [p for p, _ in rows]
    assert programs.count("chunk") == 3 and "prefill" not in programs
    assert req.output_tokens == want
    last_chunk = max(i for i, p in enumerate(programs) if p == "chunk")
    res = check(ref, builder, model, rows[last_chunk:], req, prompt, 6)
    assert res["ok"] and res["rows"] == 7, res


def test_preemption_by_recompute_gives_the_same_tokens(model):
    from paddle_tpu.serving.request import SamplingParams

    calm = make_engine(model)
    prompts = [prompt_of(14, seed=s) for s in range(4)]
    want = [serve(calm, p, 12).output_tokens for p in prompts]
    tight = make_engine(model, num_blocks=26)   # 17 common blocks = 68 tokens
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
    # a preempted request gave its slot back with its pages
    assert min(held[:-1]) < 4 and held[-1] == 0
    assert tight.kv.num_free == 25


def test_a_slot_reused_after_a_finished_sequence_reads_nothing_left(model):
    eng = make_engine(model)
    first = serve(eng, prompt_of(30, seed=5), 9)
    slot = eng.kv._free_slots[-1]               # the one handed out next
    assert float(jnp.abs(eng._k_pools[0][slot]).max()) > 0    # left dirty
    prompt = prompt_of(23, seed=6)
    again = serve(eng, prompt, 9)
    fresh = serve(make_engine(model), prompt, 9)
    assert again.output_tokens == fresh.output_tokens
    assert first.output_tokens != again.output_tokens


def test_an_aborted_request_gives_its_slot_back_and_the_next_owner_is_clean(
        model):
    from paddle_tpu.serving.request import SamplingParams

    eng = make_engine(model)
    gone = eng.add_request(prompt_of(26, seed=12), SamplingParams(
        max_new_tokens=40, temperature=0.0))
    for _ in range(5):
        eng.step()
    assert eng.kv.state_slots_held == 1 and not gone.finished
    slot = eng.kv.table(gone.request_id)[0]
    eng.abort_request(gone.request_id)
    eng.step()
    assert gone.finished and eng.kv.state_slots_held == 0
    assert eng.kv.num_free == 127 and eng.kv._free_slots[-1] == slot
    prompt = prompt_of(19, seed=13)
    again = serve(eng, prompt, 7)                   # takes the same slot
    assert again.output_tokens == serve(make_engine(model), prompt,
                                        7).output_tokens


def test_a_request_alone_and_in_a_batch_of_eight_gives_the_same(model):
    """Its prefill is the same one-row program both times, so those logits
    agree bit for bit in float32; the decode rows run in another row
    bucket (XLA's CPU matmul of 1 row and of 8 round differently in the
    last bit), so there the tokens are what is compared."""
    from paddle_tpu.serving.request import SamplingParams

    prompt = prompt_of(21, seed=11)
    alone = make_engine(model, num_blocks=256)
    rows = capture(alone)
    req = serve(alone, prompt, 8)
    want = rows[0][1]

    crowd = make_engine(model, num_blocks=256)
    rows = capture(crowd)
    greedy = SamplingParams(max_new_tokens=9, temperature=0.0)
    for s in range(7):
        crowd.add_request(prompt_of(9 + 3 * s, seed=20 + s), greedy)
    mine = crowd.add_request(prompt, greedy)
    for _ in range(80):
        if mine.finished:
            break
        crowd.step()
    assert mine.output_tokens == req.output_tokens
    assert max(l.shape[0] for p, l in rows if p == "decode") == 8
    assert any((l == want).all() for p, l in rows if p == "prefill")


def test_three_hundred_decode_steps_stay_on_the_reference(ref, builder, model):
    eng = make_engine(model, num_blocks=256)
    rows = capture(eng)
    prompt = prompt_of(20, seed=9)
    req = serve(eng, prompt, 300)
    assert [p for p, _ in rows].count("decode") == 300
    res = check(ref, builder, model, rows, req, prompt, 300)
    assert res["ok"] and res["rows"] == 301, res
    # no drift: the last fifty rows are as close as the first
    ids = prompt + [int(t) for t in req.output_tokens[:300]]
    full = np.asarray(ref.reference_logits(
        builder.reference_weights(model), TINY, ids))[len(prompt) - 1:]
    got = np.stack([l if l.ndim == 1 else l[0] for _, l in rows])
    assert np.abs(got[-50:] - full[-50:]).max() < 1e-5


def test_build_phase_carries_the_slot_integers(model):
    eng = make_engine(model)
    seen = []
    real = eng.tracer.phase

    def phase(name, recorder=None, **ints):
        if name == "engine.build":
            seen.append(ints)
        return real(name, recorder, **ints)

    eng.tracer.phase = phase
    serve(eng, prompt_of(11), 3)
    assert seen[0] == {"state_rows": 1, "state_slots_held": 0}
    assert seen[1:] == [{"rows": 1, "state_rows": 1, "state_slots_held": 1}] * 3
    text = eng.metrics.registry.prometheus_text()
    assert "serving_state_slots_held 0" in text       # finished: none held


# --- the faults the comparison must catch ----------------------------------------

FAULTS = ["none", "state_in_bf16", "pad_advances_state", "stale_slot",
          "no_norms", "no_D", "no_conv_bias", "no_dt_bias", "rope_applied",
          "order_shifted"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_fails_the_comparison(ref, builder, model, fault,
                                            monkeypatch):
    """One request dirties the slots, then a 37-token prompt (27 pad
    positions in its bucket) and 24 decode steps through slots and pages,
    every launch compared."""
    planted_fault(ref, builder, model, fault, monkeypatch)


def planted_fault(ref, builder, model, fault, monkeypatch, **engine_kw):
    from paddle_tpu.models import llama, mamba_hybrid

    cfg = dict(TINY)
    if fault == "order_shifted":
        cfg["attn_layer_offset"] = 2
    broken = builder.build(cfg, 7, dtype="float32")
    named = dict(broken.named_parameters())
    zero = lambda suffix: [p.set_value(jnp.zeros_like(p._value))
                           for n, p in named.items() if n.endswith(suffix)]
    if fault == "state_in_bf16":
        through = lambda h: h.astype(jnp.bfloat16).astype(jnp.float32)
        scan, step = mamba_hybrid.selective_scan, mamba_hybrid.selective_step

        def scan16(*a):
            y, h = scan(*a)
            return y, through(h)

        def step16(*a):
            y, h = step(*a)
            return y, through(h)

        monkeypatch.setattr(mamba_hybrid, "selective_scan", scan16)
        monkeypatch.setattr(mamba_hybrid, "selective_step", step16)
    elif fault == "pad_advances_state":
        real = mamba_hybrid.selective_scan
        monkeypatch.setattr(
            mamba_hybrid, "selective_scan",
            lambda x, dt, A, B, C, h0, n_valid: real(x, dt, A, B, C, h0,
                                                     x.shape[1]))
    elif fault == "stale_slot":
        monkeypatch.setattr(mamba_hybrid, "_carried_state",
                            lambda cache, h, w, decode: (h, w))
    elif fault == "no_norms":
        monkeypatch.setattr(mamba_hybrid, "_rms",
                            lambda x, w, eps: x.astype(jnp.float32))
    elif fault == "no_D":
        zero("mamba.D")
    elif fault == "no_conv_bias":
        zero("mamba.conv_bias")
    elif fault == "no_dt_bias":
        zero("dt_proj.bias")
    elif fault == "rope_applied":
        broken.config.use_rope = True
        for layer in broken.llama.layers:
            if hasattr(layer, "self_attn"):
                att = layer.self_attn
                att._rope_cos, att._rope_sin = llama._rope_tables(16, 512, 1e4)
    eng = make_engine(broken, **engine_kw)
    serve(eng, prompt_of(50, seed=40), 6)
    rows = capture(eng)
    prompt = prompt_of(37, seed=41)
    req = serve(eng, prompt, 24)
    res = check(ref, builder, model, rows, req, prompt, 24)
    assert res["rows"] == 25
    assert res["ok"] == (fault == "none"), (fault, res)


# --- the decode step forced through the in-place kernel (ops/pallas_ssm.py) --------
# TINY's widths are whole float32 tiles (8 state indices, 128 channels): off the
# chip only the force takes the kernel, in interpret mode.

FORCED = dict(use_pallas_paged=True)


def decode_traces(engine):
    """The attributes of every ``decode_jit_trace`` instant the engine
    emits from here on."""
    seen, real = [], engine.tracer.instant

    def instant(name, **kw):
        if name == "decode_jit_trace":
            seen.append(kw)
        return real(name, **kw)

    engine.tracer.instant = instant
    return seen


def test_forced_kernel_twenty_decode_steps_stay_on_the_reference(
        ref, builder, model):
    from paddle_tpu.ops import selective_scan

    eng = make_engine(model, num_blocks=256, **FORCED)
    seen = decode_traces(eng)
    rows = capture(eng)
    prompt = prompt_of(9, seed=9)       # 29 tokens: one table width
    req = serve(eng, prompt, 20)
    assert selective_scan.last_path == "pallas"
    assert seen and all(kw["state_step"] == "pallas" for kw in seen)
    assert [p for p, _ in rows].count("decode") == 20
    res = check(ref, builder, model, rows, req, prompt, 20)
    assert res["ok"] and res["rows"] == 21, res


def test_forced_kernel_in_a_batch_of_eight_gives_the_xla_tokens(model):
    """The tokens of a request through the kernel among seven others
    (neighbours on other slots, padding rows on the null slot as the rows
    grow from 1 to 8) are those of the XLA path alone."""
    from paddle_tpu.serving.request import SamplingParams

    prompt = prompt_of(10, seed=11)     # 31 tokens at most: one table width
    want = serve(make_engine(model, num_blocks=256), prompt, 20).output_tokens

    crowd = make_engine(model, num_blocks=256, **FORCED)
    rows = capture(crowd)
    greedy = SamplingParams(max_new_tokens=21, temperature=0.0)
    for s in range(7):
        crowd.add_request(prompt_of(4 + s, seed=20 + s), greedy)
    mine = crowd.add_request(prompt, greedy)
    for _ in range(120):
        if mine.finished:
            break
        crowd.step()
    assert mine.output_tokens == want
    assert max(l.shape[0] for p, l in rows if p == "decode") == 8


def test_the_xla_engine_says_so_on_its_trace_instant(model):
    eng = make_engine(model)
    seen = decode_traces(eng)
    serve(eng, prompt_of(11), 2)
    assert [kw["state_step"] for kw in seen] == ["xla"]


@pytest.mark.parametrize("fault", ["stale_slot", "pad_advances_state"])
def test_planted_fault_fails_the_comparison_through_the_kernel(
        ref, builder, model, fault, monkeypatch):
    planted_fault(ref, builder, model, fault, monkeypatch, **FORCED)
