"""Sliding-window layers beside global ones in ONE allocator, the parallel
attention + expert block and a chip's share of the routed experts, on the
serving path: what the layers declare (a ring a sequence beside pages a
token), the engine's prefill, chunk, decode and recompute programs against
the plain reference (``benchmarks/reference/window_moe_decoder.py``), the
shares of the expert layer adding up, the integers the phases carry, the
refusals by name and the kernel compiled for the chip at the cell's shapes
(token identity and the planted faults are in
``test_zzzzzzzzzzzzzzzzzz_window_moe_faults.py``).  float32 on the CPU,
tiny widths: a window of 8, two periods of the layer pattern, 3 of 8
experts held."""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import harness

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


# --- what the layers declare, and what is allocated from it --------------------

def test_layers_declare_a_ring_or_pages_in_the_published_order(model):
    from paddle_tpu.ops.paged_attention import CacheSpec

    ring = CacheSpec(state=(((8, 2, 16), None), ((8, 2, 16), None)), window=8)
    pages = CacheSpec(k=(2, 16), v=(2, 16))
    assert model.cache_specs() == [ring, ring, ring, pages] * 2
    assert [l.window for l in model.llama.layers] == [8, 8, 8, None] * 2
    assert ring.values_per_token() == 0 and pages.values_per_token() == 64
    assert ring.state_bytes_per_sequence("bfloat16") == 2 * 8 * 2 * 16 * 2
    with pytest.raises(ValueError, match="under state"):
        CacheSpec(k=(2, 16), v=(2, 16), window=8)


def test_the_published_widths_declare_the_arithmetic_of_the_cell():
    """A window layer's memory a sequence is the ring and does not grow
    past the window: 16,777,216 B a layer, 50,331,648 B over the three of
    a period, whatever the length; the global layer holds 4,096 B a
    token."""
    from paddle_tpu.models import ParallelWindowMoELayer, WindowMoEConfig

    cfg = WindowMoEConfig(num_hidden_layers=4, max_position_embeddings=8192,
                          vocab_size=32768, experts_held=tuple(range(16)))
    specs = [ParallelWindowMoELayer.cache_spec(types.SimpleNamespace(
        config=cfg, window=cfg.sliding_window if cfg.is_window_layer(i)
        else None)) for i in range(4)]
    assert [s.window for s in specs] == [4096, 4096, 4096, None]
    assert sum(s.state_bytes_per_sequence("bfloat16")
               for s in specs) == 50_331_648
    assert sum(s.values_per_token() for s in specs) * 2 == 4096
    assert 33 * 50_331_648 == 1_660_944_384         # the cell's rings
    assert 16_896 * 16 * 4096 == 1_107_296_256      # and its pages


def test_engine_allocates_rings_and_pages_by_the_declaration(model):
    eng = make_engine(model)
    assert eng.state_slots == 8 and eng.kv.state_slots == 8
    ring, pages = (9, 8, 2, 16), (128, 4, 2, 16)
    assert [p.shape for p in eng._k_pools] == [ring, ring, ring, pages] * 2
    assert [p.shape for p in eng._v_pools] == [ring, ring, ring, pages] * 2
    rings = sum(p.nbytes for pools in (eng._k_pools, eng._v_pools)
                for p, s in zip(pools, eng.cache_specs) if s.state)
    held = sum(p.nbytes for pools in (eng._k_pools, eng._v_pools)
               for p, s in zip(pools, eng.cache_specs) if not s.state)
    assert rings == 9 * 6 * 2 * 8 * 2 * 16 * 4      # 9 slots of 6 rings x2
    assert held == 128 * 4 * 2 * 2 * 2 * 16 * 4     # 512 tokens of 2 layers
    text = eng.metrics.registry.prometheus_text()
    assert "serving_kv_bytes_per_token 512" in text     # 2 layers x 64 x 4 B
    assert "serving_state_slots_capacity 8" in text
    assert "serving_state_bytes_per_sequence 12288" in text  # 6 x 512 x 4 B
    assert "serving_state_slots_held 0" in text


# --- the ring's arithmetic -------------------------------------------------------

def test_ring_positions_are_the_last_window_tokens():
    from paddle_tpu.ops.window_attention import ring_positions

    got = np.asarray(ring_positions(jnp.asarray([2, 7, 8, 21]), 8))
    assert got[0].tolist() == [0, 1, 2, -5, -4, -3, -2, -1]
    assert got[1].tolist() == list(range(8))
    assert got[2].tolist() == [8, 1, 2, 3, 4, 5, 6, 7]
    assert sorted(got[3].tolist()) == list(range(14, 22))
    assert all(p % 8 == j for j, p in enumerate(got[3].tolist()))
    assert (np.asarray(ring_positions(jnp.asarray(-1), 8)) < 0).all()


def test_a_span_writes_its_last_window_real_tokens_and_nothing_else():
    from paddle_tpu.ops.window_attention import ring_write_span

    ring = jnp.full((3, 8, 1, 1), -1.0)
    new = jnp.arange(16, dtype=jnp.float32).reshape(16, 1, 1)
    out = np.asarray(ring_write_span(ring, 2, new, 0, 13))[..., 0, 0]
    assert (out[:2] == -1).all()                        # other slots
    # 13 real tokens: positions 5..12 stay, at index p mod 8
    assert out[2].tolist() == [8, 9, 10, 11, 12, 5, 6, 7]
    short = np.asarray(ring_write_span(ring, 1, new, 0, 3))[1, :, 0, 0]
    assert short.tolist() == [0, 1, 2, -1, -1, -1, -1, -1]
    # a chunk at position 6: tokens 6..9 of the sequence
    chunk = np.asarray(ring_write_span(ring, 1, new[:4], 6, 4))[1, :, 0, 0]
    assert chunk.tolist() == [2, 3, -1, -1, -1, -1, 0, 1]


@pytest.mark.parametrize("window", [None, 8, 24])
def test_banded_blocks_agree_with_one_block_over_all_keys(window, monkeypatch):
    from paddle_tpu.ops import window_attention as wa

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 8)), jnp.float32)
    idx = jnp.arange(64)
    want = wa.masked_attention(q, k, v, idx, idx, window)
    monkeypatch.setattr(wa, "SCORE_BYTES", 4 * 4 * 8 * 32)   # 8-row blocks
    got = wa.masked_attention(q, k, v, idx, idx, window, banded=True)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    # a length that is no multiple of the block
    got = wa.masked_attention(q[:, :37], k[:, :37], v[:, :37], idx[:37],
                              idx[:37], window, banded=True)
    assert np.abs(np.asarray(got) - np.asarray(want)[:, :37]).max() < 1e-5


def test_ring_decode_is_paged_decode_over_the_rings_pages():
    """Both routes of the paged decode path over the ring's paged view
    (the kernel in interpret mode) against attention written out."""
    from paddle_tpu.ops import window_attention as wa

    rng = np.random.default_rng(4)
    W, h, d = 32, 2, 128
    kr = jnp.asarray(rng.normal(size=(5, W, h, d)), jnp.float32)
    vr = jnp.asarray(rng.normal(size=(5, W, h, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(3, 8, d)), jnp.float32)
    slots, pos = jnp.asarray([4, 1, 2]), jnp.asarray([9, 31, 77])
    assert wa.ring_page(W) == 32 and wa.ring_page(4096) == 256
    outs = [np.asarray(wa.ring_decode_attention(q, kr, vr, slots, pos, f))
            for f in (True, False)]
    for b in range(3):
        n = min(int(pos[b]) + 1, W)
        kk, vv = kr[slots[b], :n], vr[slots[b], :n]
        for head in range(8):
            s = kk[:, head // 4] @ q[b, head] / np.sqrt(d)
            want = jax.nn.softmax(s) @ vv[:, head // 4]
            for out in outs:
                assert np.abs(out[b, head] - np.asarray(want)).max() < 2e-5


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


def test_a_prompt_longer_than_the_window_then_decode_that_wraps_the_ring(
        ref, builder, model):
    eng = make_engine(model)
    rows = capture(eng)
    prompt = prompt_of(21)          # bucket 32; the window is 8
    req = serve(eng, prompt, 20)    # the ring wraps twice more in decode
    assert [p for p, _ in rows] == ["prefill"] + ["decode"] * 20
    res = check(ref, builder, model, rows, req, prompt, 20)
    assert res["ok"] and res["rows"] == 21, res
    assert res["max_abs_diff"] < 5e-6
    assert eng.kv.state_slots_held == 0         # finished: the slot is back


def test_a_prompt_shorter_than_the_window_fills_the_ring_in_decode(
        ref, builder, period):
    eng = make_engine(period)
    rows = capture(eng)
    prompt = prompt_of(3, seed=8)
    req = serve(eng, prompt, 14)
    res = check(ref, builder, period, rows, req, prompt, 14, PERIOD)
    assert res["ok"] and res["rows"] == 15, res


def test_chunked_prefill_agrees_with_one_shot_and_the_reference(
        ref, builder, period):
    from paddle_tpu.serving import SchedulerConfig

    prompt = prompt_of(45, seed=1)
    want = serve(make_engine(period), prompt, 6).output_tokens
    eng = make_engine(period, scheduler=SchedulerConfig(
        max_num_seqs=8, max_prefill_tokens_per_step=16))
    rows = capture(eng)
    req = serve(eng, prompt, 6)
    programs = [p for p, _ in rows]
    assert programs.count("chunk") == 3 and "prefill" not in programs
    assert req.output_tokens == want
    last_chunk = max(i for i, p in enumerate(programs) if p == "chunk")
    res = check(ref, builder, period, rows[last_chunk:], req, prompt, 6, PERIOD)
    assert res["ok"] and res["rows"] == 7, res


def test_the_kernel_route_reads_rings_and_pages_alike(ref, builder, period):
    """``use_pallas_paged=True`` forces the paged decode kernel (interpret
    mode here) for the global layers' pages AND the window layers' rings."""
    eng = make_engine(period, use_pallas_paged=True)
    rows = capture(eng)
    prompt = prompt_of(13, seed=3)
    req = serve(eng, prompt, 10)
    assert eng.attention_paths["decode"] == "pallas"
    res = check(ref, builder, period, rows, req, prompt, 10, PERIOD)
    assert res["ok"] and res["rows"] == 11, res


# --- a chip's share of the experts ------------------------------------------------

def moe_inputs(T=50, k=2, H=16, F=12, E=8, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    ids = jnp.asarray(np.stack([rng.choice(E, k, replace=False)
                                for _ in range(T)]), jnp.int32)
    w = jnp.asarray(rng.random((T, k)), jnp.float32)
    wgu = jnp.asarray(rng.normal(size=(E, H, 2 * F)), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(E, F, H)), jnp.float32)
    return x, ids, w, wgu, wd


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_held_pairs_in_passes_are_the_one_pass_over_all_pairs(
        chunk, monkeypatch):
    from paddle_tpu.parallel import moe

    x, ids, w, wgu, wd = moe_inputs()
    held = (2, 3, 5)
    sel = jnp.asarray(held)
    want, load = moe.dropless_experts(x, ids, w, wgu[sel], wd[sel], 8, held)
    monkeypatch.setattr(moe, "ONE_PASS_PAIRS", 10)
    monkeypatch.setattr(moe, "held_pair_chunk", lambda *a: chunk)
    got, load2 = jax.jit(lambda *a: moe.dropless_experts(*a, 8, held))(
        x, ids, w, wgu[sel], wd[sel])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    assert (np.asarray(load) == np.asarray(load2)).all()
    assert int(load.sum()) == 100


def test_the_pass_follows_the_held_share_and_every_expert_held_is_one_pass(
        monkeypatch):
    from paddle_tpu.parallel import moe

    assert moe.held_pair_chunk(8192 * 8, 16, 128) == 16384
    assert moe.held_pair_chunk(2048 * 8, 16, 128) == 4096
    assert moe.held_pair_chunk(1024 * 8, 16, 128) == 2048
    x, ids, w, wgu, wd = moe_inputs()
    monkeypatch.setattr(moe, "ONE_PASS_PAIRS", 10)
    seen = []
    real = moe._held_pairs_in_chunks
    monkeypatch.setattr(moe, "_held_pairs_in_chunks",
                        lambda *a: seen.append(a[-1]) or real(*a))
    moe.dropless_experts(x, ids, w, wgu, wd, 8)             # all held
    moe.dropless_experts(x, ids, w, wgu, wd, 8, tuple(range(8)))
    assert seen == []
    sel = jnp.asarray([1, 6])
    moe.dropless_experts(x, ids, w, wgu[sel], wd[sel], 8, (1, 6))
    assert seen == [64]         # 2 x 100 pairs x 2 / 8 = 50 -> 64


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(builder):
    """Four chips of two experts each: what every share gives for its own
    experts, with the shared experts' mean (which every chip computes
    alike) counted ONCE, is what a layer holding all eight gives."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import HeldExperts

    whole_cfg = builder.model_config(dict(TINY, num_experts=8,
                                          experts_held=list(range(8))))
    paddle.seed(5)
    whole = HeldExperts(whole_cfg)
    x = Tensor(jnp.asarray(np.random.default_rng(9).normal(
        size=(2, 11, 64)), jnp.float32))
    with paddle.no_grad():
        want = np.asarray(whole(x)._value)
        shared = np.asarray(whole.shared_experts(x)._value) / 2
        total = np.zeros_like(want)
        loads = []
        for chip in range(4):
            held = [2 * chip, 2 * chip + 1]
            part = HeldExperts(builder.model_config(
                dict(TINY, num_experts=2, experts_held=held)))
            sel = jnp.asarray(held)
            part.gate.weight.set_value(whole.gate.weight._value)
            part.w_gate_up.set_value(whole.w_gate_up._value[sel])
            part.w_down.set_value(whole.w_down._value[sel])
            for name in ("gate_proj", "up_proj", "down_proj"):
                getattr(part.shared_experts, name).weight.set_value(
                    getattr(whole.shared_experts, name).weight._value)
            total += np.asarray(part(x)._value) - shared
            loads.append(np.asarray(part.load))
    assert np.abs(total + shared - want).max() < 1e-5
    assert all((l == loads[0]).all() for l in loads)    # one router
    assert int(loads[0].sum()) == 2 * 11 * 2
    assert np.abs(want - shared).max() > 1e-3           # the experts matter


def test_fetch_and_build_phases_carry_the_held_and_window_integers(period):
    eng = make_engine(period)
    seen = {"engine.build": [], "engine.fetch": []}
    real = eng.tracer.phase

    def phase(name, recorder=None, **ints):
        if name in seen:
            seen[name].append(ints)
        return real(name, recorder, **ints)

    eng.tracer.phase = phase
    serve(eng, prompt_of(5), 6)
    builds = seen["engine.build"]
    assert builds[0] == {"state_rows": 1, "state_slots_held": 0}
    # after the prompt of 5 a row reads min(6, 8), min(7, 8), 8, 8, ...
    assert [b["window_tokens"] for b in builds[1:]] == [6, 7, 8, 8, 8, 8]
    assert all(b["state_slots_held"] == 1 for b in builds[1:])
    fetches = seen["engine.fetch"]
    assert len(fetches) == 7
    for f in fetches[1:]:
        assert f["moe_decode"] == 1 and f["moe_assignments"] == 4 * 2
        assert 0 <= f["moe_pairs_held"] <= 4 * 2
        assert f["moe_held_touched"] <= min(f["moe_pairs_held"], 4 * 3)
    assert fetches[0]["moe_assignments"] == 4 * 2 * 8   # a bucket of 8
    text = eng.metrics.registry.prometheus_text()
    total = sum(f["moe_pairs_held"] for f in fetches)
    assert f"serving_moe_pairs_held_total {float(total)}" in text \
        or f"serving_moe_pairs_held_total {total}" in text
    assert "serving_moe_held_pair_share" in text


def test_a_model_that_holds_every_expert_has_no_held_series():
    builder = harness.load_module("models", "glm_moe_mla")
    cfg = harness.load_json(harness.HERE, "configs", "glm-4.7-flash.json")
    tiny = dict(cfg, vocab_size=128, hidden_size=32, intermediate_size=64,
                moe_intermediate_size=16, num_hidden_layers=2,
                num_attention_heads=2, num_key_value_heads=2,
                max_position_embeddings=64, q_lora_rank=16, kv_lora_rank=8,
                qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                n_routed_experts=4, num_experts_per_tok=2)
    eng = make_engine(builder.build(tiny, 3, dtype="float32"),
                      num_blocks=32)
    seen = []
    real = eng.tracer.phase
    eng.tracer.phase = lambda name, recorder=None, **ints: (
        seen.append((name, ints)), real(name, recorder, **ints))[1]
    serve(eng, list(range(1, 6)), 2)
    fetch = [i for n, i in seen if n == "engine.fetch"]
    assert fetch and all("moe_pairs_held" not in i and "moe_assignments" in i
                         for i in fetch)
    assert all("window_tokens" not in i for n, i in seen)
    assert "moe_pairs_held" not in eng.metrics.registry.prometheus_text()


# --- the refusals, by name -------------------------------------------------------

@pytest.mark.parametrize("kw,word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(unified_step=True), "unified_step"),
    (dict(burst_steps=4), "burst_steps"),
    (dict(role="prefill"), "KV hand-off"),
    (dict(aot_path="/nowhere"), "aot"),
])
def test_paths_without_a_form_for_the_ring_refuse_by_name(model, kw, word):
    with pytest.raises(ValueError, match="per-sequence") as e:
        make_engine(model, **kw)
    assert word in str(e.value)


def test_speculative_verify_audit_and_tensor_parallel_refuse_by_name(
        model, monkeypatch):
    from paddle_tpu.observability.audit import AuditConfig
    from paddle_tpu.parallel import utils
    from paddle_tpu.serving import handoff

    with pytest.raises(ValueError, match="per-sequence") as e:
        make_engine(model, audit=AuditConfig(enabled=True))
    assert "audit" in str(e.value)
    with pytest.raises(handoff.HandoffError, match="per-sequence"):
        handoff.pool_meta(make_engine(model))
    monkeypatch.setattr(utils, "axis_size",
                        lambda name: 2 if name == "mp" else 1)
    with pytest.raises(ValueError, match="per-sequence") as e:
        make_engine(model)
    assert "mp=2" in str(e.value)


def test_a_window_layer_refuses_pages_and_the_ragged_program(model):
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.paged_attention import PagedCache

    x = Tensor(jnp.zeros((1, 1, 64), jnp.float32))
    with paddle.no_grad(), pytest.raises(TypeError, match="StateCache"):
        model.llama.layers[0].self_attn(x, cache=PagedCache(None, None))
    ragged = PagedCache(None, None)
    ragged.seg_ids = jnp.zeros((1,), jnp.int32)
    with paddle.no_grad(), pytest.raises(NotImplementedError, match="ragged"):
        model.llama.layers[3].self_attn(x, cache=ragged)


# --- the kernel at the cell's shapes, for the chip that is not attached ------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name,rows,heads,kv_heads,blocks,page,width,dtype", [
    ("a ring as pages of 256 tokens", 32, 128, 8, 33 * 16, 256, 16, "bfloat16"),
    ("the global layer's pages at 8,192 tokens", 32, 128, 8, 16896, 16, 512,
     "bfloat16"),
    ("mistral-7b-v0.3 at 4,096 tokens", 32, 32, 8, 4800, 16, 256, "bfloat16"),
    ("deepseek-llm-7b at 1,024 tokens", 64, 32, 32, 1280, 16, 64, "bfloat16"),
    ("one key/value head under 20 query heads", 256, 20, 1, 8192, 16, 256,
     "bfloat16"),
    ("float32 pools: 8 heads at once", 8, 32, 8, 512, 16, 64, "float32"),
    ("float32 pools: 16 heads in a loop of 8", 8, 32, 16, 512, 16, 64,
     "float32"),
    ("a shard's 2 heads of 8", 8, 8, 2, 512, 16, 64, "bfloat16"),
    ("3 heads: not the group walk's", 8, 12, 3, 512, 16, 64, "bfloat16"),
])
def test_paged_decode_kernel_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, name, rows, heads, kv_heads, blocks, page,
        width, dtype):
    """The TPU compiler takes the decode kernel at every cell's shapes
    (heads of 128): the kernel of a step a (row, page) at the rings' pages,
    and at 16-token pages the group walk — the groups of pages it copies
    into VMEM, the strided read of a pair of heads' keys out of them, the
    loop over more than 8 heads, and the one-head pool it squeezes."""
    from paddle_tpu.ops import pallas_paged

    monkeypatch.setattr(pallas_paged, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        def s(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        pool = s((blocks, page, kv_heads, 128), jnp.dtype(dtype))
        compiled = jax.jit(pallas_paged.paged_attention_decode).lower(
            s((rows, heads, 128), jnp.bfloat16), pool, pool,
            s((rows, width), jnp.int32), s((rows,), jnp.int32)).compile()
        assert "tpu_custom_call" in compiled.as_text(), name
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def _steps_the_donated_pool_in_place(compiled, pool_bytes):
    """A state-step kernel is in the program and the donated pool is its
    output: nothing the size of the pool is allocated beside it."""
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 8


@pytest.mark.parametrize("rows", [8, 256])
def test_state_step_kernel_compiles_at_the_published_widths(
        one_chip, monkeypatch, rows):
    """The TPU compiler takes the selective-scan decode step in place on
    the slot pool at AI21-Jamba2-3B's widths (``ops/pallas_ssm.py``; it
    stands here because one file of a run may describe the chip), and the
    donated pool is the kernel's output: nothing the size of the pool is
    allocated beside it."""
    from paddle_tpu.ops import pallas_ssm

    monkeypatch.setattr(pallas_ssm, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        def s(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        n, d, slots = 16, 5120, 257
        compiled = jax.jit(pallas_ssm.state_step, donate_argnums=5).lower(
            s((rows, d)), s((rows, d)), s((n, d)), s((rows, n)), s((rows, n)),
            s((slots, n, d)), s((rows,), jnp.int32)).compile()
        _steps_the_donated_pool_in_place(compiled, slots * n * d * 4)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("rows", [8, 128])
def test_delta_state_step_kernel_compiles_at_the_published_widths(
        one_chip, monkeypatch, rows):
    """The TPU compiler takes the gated delta rule's decode step in place
    on the slot pool at GigaChat3.5-432B-A28B's widths (64 value heads on
    32 key heads, states of 128 x 128 float32, the cell's 128 slots and
    the null one: ``ops/pallas_gated_delta.py``, here for the reason
    above), and the donated pool is the kernel's output: nothing the size
    of the pool, or of the rows' gathered states, is allocated beside it."""
    from paddle_tpu.ops import pallas_gated_delta

    monkeypatch.setattr(pallas_gated_delta, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        def s(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        hk, hv, d, slots = 32, 64, 128, 129
        compiled = jax.jit(pallas_gated_delta.state_step,
                           donate_argnums=5).lower(
            s((rows, hk, d)), s((rows, hk, d)), s((rows, hv, d)),
            s((rows, hv)), s((rows, hv)), s((slots, hv, d, d)),
            s((rows,), jnp.int32)).compile()
        assert "gdn_state_step" in compiled.as_text()
        _steps_the_donated_pool_in_place(compiled, slots * hv * d * d * 4)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("name,tokens,heads,nope,vd,dtype", [
    ("xing4.0-29b-a4b, the 4,096 bucket", 4096, 32, 128, 128, "bfloat16"),
    ("xing4.0-29b-a4b, the 2,048 bucket", 2048, 32, 128, 128, "bfloat16"),
    ("glm-4.7-flash, the 1,024 bucket", 1024, 20, 192, 256, "bfloat16"),
    ("one block of 128, float32", 128, 4, 192, 256, "float32"),
])
def test_flash_prefill_compiles_at_the_latent_cells_shapes(
        one_chip, monkeypatch, name, tokens, heads, nope, vd, dtype):
    """The TPU compiler takes the expanded latent prefill with its kernel
    (``ops/pallas_flash.py`` ``flash_prefill``, here for the reason above)
    at both latent cells' head sizes — 192 and 64 are no multiples of 128
    lanes — with ``q_start`` a traced scalar, as the prefill program
    passes it, and the program holds no scores: its temporaries are the
    rebuilt keys and values and the layout copies, not ``[heads, S, M]``."""
    from paddle_tpu.ops import paged_attention as ops
    from paddle_tpu.ops import pallas_flash

    monkeypatch.setattr(pallas_flash, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        def s(shape, dt=jnp.dtype(dtype)):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        rank, rope = 512, 64
        compiled = jax.jit(
            lambda q, lat, wk, wv, start: ops.latent_expanded_attention(
                q, lat, (wk, wv), rank, 0.0722, start, use_pallas=True)
        ).lower(s((1, tokens, heads, nope + rope)),
                s((1, tokens, rank + rope)), s((heads, rank, nope)),
                s((heads, rank, vd)), s((), jnp.int32)).compile()
        assert "flash_prefill" in compiled.as_text(), name
        scores = heads * tokens * tokens * 4
        assert compiled.memory_analysis().temp_size_in_bytes < max(
            scores // 4, 8 << 20), name
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("name,rows,heads,nope,vd,width,dtype", [
    ("glm-4.7-flash, 128 rows behind tables of 4,096 tokens", 128, 20, 192,
     256, 256, "bfloat16"),
    ("glm-4.7-flash, the set-up check's one row", 1, 20, 192, 256, 64,
     "bfloat16"),
    ("xing4.0-29b-a4b, 8 rows behind tables of 8,192 tokens", 8, 32, 128,
     128, 512, "bfloat16"),
    ("float32 pools", 8, 20, 192, 256, 64, "float32"),
])
def test_latent_decode_walk_compiles_at_the_latent_cells_shapes(
        one_chip, monkeypatch, name, rows, heads, nope, vd, width, dtype):
    """The TPU compiler takes a token's write and the absorbed latent
    decode with its kernel (``ops/pallas_paged.py``
    ``latent_decode_attention``, here for the reason above) on the pool as
    the engine holds it, at both latent cells' head counts: the resident
    array lies row-major, so the program holds NO copy of it and nothing
    the size of a gathered context, and the custom call keeps the scope its
    readers look for."""
    from paddle_tpu.ops import paged_attention as ops
    from paddle_tpu.ops import pallas_paged

    monkeypatch.setattr(pallas_paged, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        def s(shape, dt=jnp.dtype(dtype)):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        rank, rope, blocks, bs = 512, 64, 19200, 16
        shape = ops.latent_pool_shape(blocks, bs, (1, rank + rope))
        assert shape == (blocks, bs, 640)

        def step(pool, new, slot_blocks, offs, q, wk, wv, tables, lens):
            pool = pool.at[slot_blocks, offs].set(ops.pool_rows(new, pool))
            with jax.named_scope("attn"), jax.named_scope("mla_decode_core"):
                return pool, ops.latent_paged_decode_attention(
                    q, pool, (wk, wv), tables, lens, rank, 0.0625,
                    use_pallas=True)

        ints = lambda *sh: s(sh, jnp.int32)
        compiled = jax.jit(step, donate_argnums=0).lower(
            s(shape), s((rows, 1, rank + rope)), ints(rows), ints(rows),
            s((rows, heads, nope + rope)), s((heads, rank, nope)),
            s((heads, rank, vd)), ints(rows, width), ints(rows)).compile()
        text = compiled.as_text()
        call = [l for l in text.splitlines()
                if "custom-call(" in l and "latent_decode_attention" in l]
        assert len(call) == 1, name
        assert "attn/mla_decode_core/" in call[0], name
        entry = next(l for l in text.splitlines()
                     if "parameter(0), sharding" in l)
        assert "640]{2,1,0:" in entry, entry        # row-major, as declared
        assert not [l for l in text.splitlines()
                    if " copy(" in l and f"[{blocks},{bs}," in l], name
        mem = compiled.memory_analysis()
        pool_bytes = blocks * bs * 640 * jnp.dtype(dtype).itemsize
        assert mem.alias_size_in_bytes >= pool_bytes
        assert mem.temp_size_in_bytes < pool_bytes // 64, name
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("name,rows,blocks,per_block", [
    ("evabyte-6.5b, 16 rows over a pool of 136 whole tiles", 16, 34816, 1),
    ("evabyte-6.5b, the set-up check's one row", 1, 34816, 1),
    ("a pool of 136 tiles and 100 rows", 16, 34916, 1),
    ("two rows a block, a pool of 39 tiles and 16 rows", 8, 5000, 2),
    ("a pool of one tile of 128 and 72 rows", 16, 200, 1),
])
def test_chunk_summarised_decode_compiles_at_the_byte_cells_shapes(
        one_chip, monkeypatch, name, rows, blocks, per_block):
    """The TPU compiler takes the chunk-summarised decode's two kernels
    (``ops/pallas_eva.py``, here for the reason above) on the rings and the
    pool of rows as the engine holds them, at EvaByte's 32 heads of 128 --
    among them pools whose rows are no multiple of the tile (a deployment's
    ``num_blocks`` comes from its memory and rarely is one: the rows past
    the last whole tile are XLA's).  The program holds NO copy of a ring or of
    the pool and nothing their size, and both custom calls and the mask of
    who sees what keep the scopes the benchmark's readers time them by."""
    from paddle_tpu.ops import eva_attention, pallas_eva

    monkeypatch.setattr(pallas_eva, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        def s(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        slots, window, heads, dim, width = 17, 2048, 32, 128, 2048
        tile = pallas_eva.pool_tile_rows(blocks * per_block, heads, dim)
        assert tile == min(256, blocks * per_block // 128 * 128)

        def step(q, k_ring, v_ring, k_rows, v_rows, slot, tables, pos):
            with jax.named_scope("attn"):
                return eva_attention.decode_attention(
                    q, k_ring, v_ring, k_rows, v_rows, slot, tables, pos,
                    window, 16, use_pallas=True)

        ints = lambda *sh: s(sh, jnp.int32)
        ring = s((slots, window, heads, dim))
        pool = s((blocks, per_block, heads, dim))
        assert pallas_eva.takes(s((rows, heads, dim)), ring, pool)
        compiled = jax.jit(step).lower(
            s((rows, heads, dim)), ring, ring, pool, pool, ints(rows),
            ints(rows, width), ints(rows)).compile()
        lines = compiled.as_text().splitlines()
        calls = [l for l in lines
                 if "custom-call(" in l and "tpu_custom_call" in l]
        assert len(calls) == 2, name
        assert ["attn/eva_attn/eva_remote/" in l and "eva_pool_attention" in l
                for l in calls] == [True, False], name
        assert ["attn/eva_attn/eva_local/" in l and "eva_ring_attention" in l
                for l in calls] == [False, True], name
        # the serial scatter that says who holds what is the remote half's
        scatters = [l for l in lines if "jit(step)" in l and "scatter" in l]
        assert scatters and all("attn/eva_attn/eva_remote/" in l
                                for l in scatters), name
        assert not [l for l in lines if " copy(" in l and (
            f"[{blocks}," in l or f"[{slots},{window}," in l)], name
        pool_bytes = blocks * per_block * heads * dim * 2
        assert compiled.memory_analysis().temp_size_in_bytes < max(
            pool_bytes // 8, 4 << 20), name
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("name,rows,experts,h,f,limit,dtype", [
    ("glm-4.7-flash, 128 decode rows x 4", 512, 64, 2048, 1536, None,
     "bfloat16"),
    ("glm-4.7-flash, the set-up check's one row", 4, 64, 2048, 1536, None,
     "bfloat16"),
    ("glm-4.7-flash, the 4,096 prefill bucket", 16384, 64, 2048, 1536, None,
     "bfloat16"),
    ("gigachat3.5-432b-a28b, 128 decode rows x 8 on 16 held", 1024, 16, 7168,
     2048, 10.0, "bfloat16"),
    ("command-a-plus-05-2026, 32 decode rows x 8 on 16 held", 256, 16, 4096,
     4096, None, "bfloat16"),
    ("command-a-plus-05-2026, a pass of 8,192 held pairs", 8192, 16, 4096,
     4096, None, "bfloat16"),
    ("xing4.0-29b-a4b, the 4,096 prefill bucket", 16384, 64, 3584, 1024,
     None, "bfloat16"),
    ("float32 operands", 64, 8, 256, 128, None, "float32"),
])
def test_grouped_matmul_compiles_at_the_expert_cells_shapes(
        one_chip, monkeypatch, name, rows, experts, h, f, limit, dtype):
    """The TPU compiler takes both grouped products of a routed-expert layer
    through ``ops/pallas_moe.py`` (here for the reason above) at the shapes
    of the four configurations with routed experts, decode and prefill, as
    ``parallel.moe._grouped_swiglu`` chooses them by its rule; both custom
    calls keep the scope the benchmark's readers time them by, and
    ``ragged-dot`` is not in the program."""
    from paddle_tpu.ops import pallas_moe
    from paddle_tpu.parallel import moe

    monkeypatch.setattr(pallas_moe, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_moe, "_on_tpu", lambda: True)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        def s(shape, dt=jnp.dtype(dtype)):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        def layer(x, wgu, wd, sizes):
            with jax.named_scope("mlp"):
                return moe._grouped_swiglu(x, wgu, wd, sizes, limit)

        compiled = jax.jit(layer).lower(
            s((rows, h)), s((experts, h, 2 * f)), s((experts, f, h)),
            s((experts,), jnp.int32)).compile()
        assert moe.last_path == "pallas", name
        lines = compiled.as_text().splitlines()
        calls = [l for l in lines
                 if "custom-call(" in l and "tpu_custom_call" in l]
        assert len(calls) == 2, name
        assert all("mlp/moe_experts/" in l and "moe_grouped_matmul" in l
                   for l in calls), name
        assert not [l for l in lines if "ragged" in l], name
        # the weights are read where they lie: nothing their size beside them
        # (its temporaries are the rows between the two products)
        assert compiled.memory_analysis().temp_size_in_bytes < max(
            experts * 3 * h * f // 8, 4 * rows * (h + 3 * f), 1 << 20), name
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
