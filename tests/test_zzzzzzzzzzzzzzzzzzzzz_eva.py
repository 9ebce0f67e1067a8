"""Chunk-summarised (EVA) attention on the serving path, first file (the
second is ``test_zzzzzzzzzzzzzzzzzzzzz_eva_engine.py``): the plain
reference against a naive all-pairs form of the equations; what a layer
declares and what ``CacheSpec`` still refuses; the layer's paths -- the
cache-less forward, a prompt in one launch, a prompt window by window, a
prompt in chunks, and decode across a chunk's and a window's end --
against the reference's full forward pass; the faults the comparison must
catch; and the accepted layer kinds' programs, unchanged."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from eva_common import (  # noqa: F401  (fixtures among them)
    ATOL,
    FAULTS,
    RMS_REL,
    TINY,
    builder,
    capture,
    forward,
    make_engine,
    model,
    naive_logits,
    prompt_of,
    ref,
    serve,
    served_logits,
)


# --- the reference against the equations, all pairs ---------------------------------

@pytest.fixture(scope="module")
def weights(builder, model):
    return builder.reference_weights(model)


@pytest.fixture(scope="module")
def ids():
    return prompt_of(100, seed=3)       # 3 windows and 4 bytes; 6 chunks


@pytest.fixture(scope="module")
def ref_logits(ref, weights, ids):
    return np.asarray(ref.reference_logits(weights, TINY, ids))


def test_the_reference_is_the_naive_all_pairs_form(ref, weights, ids,
                                                   ref_logits):
    want = naive_logits(weights, TINY, ids)
    res = ref.compare(ref_logits, want, ATOL, RMS_REL)
    assert res["ok"] and res["rows"] == 100, res
    # and its query blocks are walked: a block shorter than a window
    assert ref.QUERY_BLOCK > TINY["window_size"]
    old, ref.QUERY_BLOCK = ref.QUERY_BLOCK, 8
    try:
        again = np.asarray(ref.reference_logits(weights, TINY, ids))
    finally:
        ref.QUERY_BLOCK = old
    assert ref.compare(again, want, ATOL, RMS_REL)["ok"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(ref, weights, ids, ref_logits,
                                              model, fault):
    """Each fault in the equations moves the logits far past the limits,
    against the reference and against what the program serves."""
    wrong = naive_logits(weights, TINY, ids, fault=fault)
    for got in (ref_logits, forward(model, ids)):
        res = ref.compare(got, wrong, ATOL, RMS_REL)
        assert not res["ok"] and res["max_abs_diff"] > 100 * ATOL, res
    # ... and only past the first window's end: inside it no summary is
    # visible and a sliding window is the aligned one (but where the fault
    # IS a summary seen early)
    if fault != "early":
        W = TINY["window_size"]
        assert ref.compare(ref_logits[:W], wrong[:W], ATOL, RMS_REL)["ok"]


def test_compare_holds_both_limits(ref):
    want = np.random.default_rng(0).normal(0, 1, (4, 96)).astype(np.float32)
    assert ref.compare(want, want, 1e-6, 1e-6)["ok"]
    one = want.copy()
    one[2, 5] += 0.5            # one logit far off: atol, not the rms
    res = ref.compare(one, want, 0.2, 0.05)
    assert not res["ok"] and res["rms_rel"] < 0.05 < 0.2 < res["max_abs_diff"]
    res = ref.compare(want * 1.1, want, 1.0, 0.05)      # all a little off
    assert not res["ok"] and res["max_abs_diff"] < 1.0
    bad = want.copy()
    bad[0, 0] = np.nan
    assert not ref.compare(bad, want, 1e9, 1e9)["ok"]


# --- what a layer declares ---------------------------------------------------------------

def test_a_layer_declares_a_ring_and_rows_at_once(model):
    from paddle_tpu.ops.paged_attention import CacheSpec

    specs = model.cache_specs()
    assert len(specs) == 2 and all(s.ring_and_rows for s in specs)
    s = specs[0]
    assert s.k == s.v == (2, 32) and s.window == 32 and s.tokens_per_row == 16
    assert s.state == (((32, 2, 32), None), ((32, 2, 32), None))
    assert s.rows_per_block(16) == 1 and s.rows_per_block(64) == 4
    assert s.values_per_token() == 2 * 2 * 32 // 16
    assert s.state_bytes_per_sequence(jnp.bfloat16) == 2 * 32 * 2 * 32 * 2
    with pytest.raises(ValueError, match="no multiple"):
        s.rows_per_block(24)
    # and what it refused it still refuses
    ring = ((8, 2, 16), None)
    with pytest.raises(ValueError, match="not both"):
        CacheSpec(k=(2, 16), v=(2, 16), state=(ring, ring))
    with pytest.raises(ValueError, match="not both"):
        CacheSpec(k=(2, 16), state=(ring, ring), window=8)
    with pytest.raises(ValueError, match="declare it under state"):
        CacheSpec(k=(2, 16), v=(2, 16), window=8)
    with pytest.raises(ValueError, match="declares no cache"):
        CacheSpec()
    with pytest.raises(ValueError, match="two"):
        CacheSpec(state=(ring,))
    for missing in (dict(state=(ring, ring), window=8),
                    dict(k=(2, 16), v=(2, 16)),
                    dict(k=(2, 16), v=(2, 16), state=(ring, ring))):
        with pytest.raises(ValueError, match="beside a window's ring"):
            CacheSpec(tokens_per_row=4, **missing)
    assert not CacheSpec(k=(2, 16), v=(2, 16)).ring_and_rows
    assert not CacheSpec(state=(ring, ring), window=8).ring_and_rows


def test_the_engine_allocates_from_the_declaration_and_refuses_by_name(model):
    from paddle_tpu.serving import SchedulerConfig

    eng = make_engine(model, num_blocks=40,
                      scheduler=SchedulerConfig(max_num_seqs=3))
    for side in (eng._k_pools, eng._v_pools):
        assert len(side) == 2
        for ring, rows in side:
            assert ring.shape == (4, 32, 2, 32) and rows.shape == (40, 1, 2, 32)
    wide = make_engine(model, num_blocks=12, block_size=32)
    assert wide._k_pools[0][1].shape == (12, 2, 2, 32)   # two rows a block
    with pytest.raises(ValueError, match="no multiple"):
        make_engine(model, block_size=8)
    from paddle_tpu.observability.audit import AuditConfig
    for kw, name in ((dict(prefix_cache=True), "prefix_cache"),
                     (dict(unified_step=True), "unified_step"),
                     (dict(burst_steps=4), "burst_steps"),
                     (dict(role="decode"), "hand-off"),
                     (dict(audit=AuditConfig(enabled=True)), "audit"),
                     (dict(aot_path="/nowhere"), "aot")):
        with pytest.raises(ValueError, match=name):
            make_engine(model, **kw)


def test_a_layer_takes_only_its_own_cache(model):
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.paged_attention import PagedCache

    x = Tensor(jnp.zeros((1, 1, 64), jnp.float32))
    with paddle.no_grad(), pytest.raises(TypeError, match="EvaCache"):
        model.llama.layers[0].self_attn(x, cache=PagedCache(None, None))


def test_rotation_is_computed_from_the_positions_and_no_table_is_built(model):
    from paddle_tpu.models.llama import _apply_rope, _rope_tables
    from paddle_tpu.ops import eva_attention as eva

    att = model.llama.layers[0].self_attn
    assert not hasattr(att, "_rope") and not hasattr(att, "_rope_cos")
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 5, 2, 32)),
                    jnp.float32)
    pos = jnp.asarray([[0, 1, 2, 3, 4], [400, 401, 402, 403, 404]])
    cos, sin = _rope_tables(32, 512, 10000.0)
    want = _apply_rope(x, jnp.asarray(cos)[pos], jnp.asarray(sin)[pos])
    np.testing.assert_allclose(eva.rotate(x, pos, 10000.0), want, atol=2e-5)
    np.testing.assert_allclose(eva.rotate(x[:1], pos[0], 10000.0), want[:1],
                               atol=2e-5)
    # the 32,768 positions of the published model cost a program nothing
    text = str(jax.make_jaxpr(lambda a, p: eva.rotate(a, p, 1e5))(x, pos))
    assert "32768" not in text and "512" not in text


# --- the layer's paths against the reference's full forward pass ----------------------

def test_the_cacheless_forward_agrees_with_the_reference(ref, model, ids,
                                                         ref_logits):
    res = ref.compare(forward(model, ids), ref_logits, ATOL, RMS_REL)
    assert res["ok"] and res["rows"] == 100, res


def check(ref, weights, rows, req, prompt, steps):
    seq = prompt + [int(t) for t in req.output_tokens[:steps]]
    full = np.asarray(ref.reference_logits(weights, TINY, seq))
    return ref.compare(served_logits(rows, steps), full[len(prompt) - 1:],
                       ATOL, RMS_REL)


@pytest.fixture(scope="module")
def engine(model):
    """ONE engine for the one-launch paths: its programs compile once."""
    eng = make_engine(model)
    return eng, capture(eng)


@pytest.mark.parametrize("n,steps,why", [
    (70, 30, "a prompt of 3 windows (bucket 128: by windows), decode over "
             "the 4th window's start and two chunk ends"),
    (5, 45, "a prompt shorter than a chunk; decode closes a chunk, then "
            "the first window"),
    (31, 3, "decode writes the window's last byte, then opens the next"),
    (64, 2, "a prompt that ends with its window: the ring starts over"),
    (15, 2, "decode completes the first chunk"),
])
def test_prefill_then_decode_agrees_with_the_reference(ref, weights, engine,
                                                       n, steps, why):
    eng, rows = engine
    del rows[:]
    prompt = prompt_of(n, seed=n)
    req = serve(eng, prompt, steps)
    assert [p for p, _ in rows] == ["prefill"] + ["decode"] * steps
    res = check(ref, weights, rows, req, prompt, steps)
    assert res["ok"] and res["rows"] == steps + 1, (why, res)


def test_a_prompt_longer_than_a_window_is_carried_window_by_window(model):
    """The one-launch prefill of a bucket past the window runs the whole
    layer under a scan over windows (``eva_by_windows``); a bucket inside
    the window runs it once."""
    layer = model.llama.layers[0]
    seen = []
    orig = type(layer)._by_windows

    def spy(self, x, cache):
        seen.append(x.shape[1])
        return orig(self, x, cache)

    type(layer)._by_windows = spy
    try:
        eng = make_engine(model)
        serve(eng, prompt_of(40, seed=1), 1)        # bucket 64 = 2 windows
        serve(eng, prompt_of(20, seed=2), 1)        # bucket 32 = 1 window
    finally:
        type(layer)._by_windows = orig
    assert seen == [64, 64]                         # both layers, once


@pytest.mark.parametrize("budget", [24, 40, 64])
def test_a_prompt_in_chunks_agrees_with_the_reference(ref, weights, model,
                                                      budget):
    """Chunks that start inside a chunk (24), cross a window's end (40)
    and hold two whole windows (64): the ring and the rows carry the
    sequence's earlier part."""
    from paddle_tpu.serving import SchedulerConfig

    eng = make_engine(model, scheduler=SchedulerConfig(
        max_num_seqs=4, max_prefill_tokens_per_step=budget))
    rows = capture(eng)
    prompt = prompt_of(100, seed=budget)
    req = serve(eng, prompt, 8)
    assert [p for p, _ in rows].count("chunk") == -(-100 // budget)
    res = check(ref, weights, rows, req, prompt, 8)
    assert res["ok"] and res["rows"] == 9, res


@pytest.mark.parametrize("form,kw,kernels", [
    ("one row a block, a pool of many times what the one row's table "
     "reaches (the cell's check: one request at a time)",
     dict(num_blocks=512), False),
    ("two rows a block", dict(block_size=32, num_blocks=24), False),
    ("the kernels: one row a block, a pool of eight tiles of which the one "
     "row sees one", dict(num_blocks=512), True),
    ("the kernels: two rows a block, a pool that ends inside its one tile",
     dict(block_size=32, num_blocks=24), True),
])
def test_the_rows_read_where_they_lie_agree_with_the_reference(
        ref, weights, model, monkeypatch, form, kw, kernels):
    """A decode launch reads ring and rows WHERE THEY LIE, in one form
    whatever the launch's rows and the pool's size, so what a check of one
    request compares is what a full launch runs.  On the CPU that form is
    XLA's (every ring whole, the whole pool under a mask of who holds
    what); on a TPU it is ``ops/pallas_eva.py``'s two kernels (the row's
    ring slot up to its position, the tiles of the pool somebody sees,
    merged by their log-sum-exp), forced here in interpret mode through
    the engine.  Both against the float32 reference."""
    from paddle_tpu.ops import eva_attention as eva
    from paddle_tpu.ops import paged_attention as paged

    if kernels:
        real = eva.decode_attention
        monkeypatch.setattr(
            eva, "decode_attention",
            lambda *a, **k: real(*a, **dict(k, use_pallas=True)))
    eng = make_engine(model, **kw)
    rows = capture(eng)
    prompt = prompt_of(70, seed=7)
    req = serve(eng, prompt, 30)
    assert paged.last_path == ("pallas" if kernels else "xla")
    res = check(ref, weights, rows, req, prompt, 30)
    assert res["ok"] and res["rows"] == 31, (form, res)


# --- the accepted layer kinds' programs do not change -------------------------------------

KINDS = ["LlamaConfig", "MoEMLAConfig", "HybridMambaConfig",
         "WindowMoEConfig", "HCMoEMLAConfig"]


def parents_init(self, config):
    """``LlamaForCausalLM.__init__`` as the parent commit (98657eb) had
    it: no ``make_lm_head`` looked for."""
    from paddle_tpu.models.llama import LlamaModel
    from paddle_tpu.nn.initializer import Normal
    from paddle_tpu.nn.layers import Layer
    from paddle_tpu.parallel.mp_layers import ColumnParallelLinear

    Layer.__init__(self)
    self.config = config
    self.llama = LlamaModel(config)
    if config.tie_word_embeddings:
        self.lm_head = None
    else:
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=True,
            weight_attr=Normal(0.0, config.initializer_range))


@pytest.mark.parametrize("kind", KINDS)
def test_an_accepted_kind_traces_to_the_parents_program(kind, monkeypatch):
    """Two traces made in THIS process (the text of a jaxpr is no constant
    of a program across processes): the model as this commit builds it
    against the same configuration under the parent's constructor -- the
    one place of the skeleton this PR touched is a hook the accepted
    configurations do not bring, so nothing of theirs is built or traced
    differently.  (The five kinds serve the six accepted configurations:
    the two dense ones are one kind.)"""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.core.tensor import Tensor

    cfg = getattr(models, kind).tiny()
    assert not hasattr(cfg, "make_lm_head")
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 96, (1, 24)))

    def trace():
        paddle.seed(5)
        m = models.LlamaForCausalLM(cfg)
        m.eval()
        params = list(m.parameters())

        def f(vals, tok):
            saved = [p._value for p in params]
            for p, v in zip(params, vals):
                p._value = v
            try:
                with paddle.no_grad():
                    return m(Tensor(tok))._value
            finally:
                for p, v in zip(params, saved):
                    p._value = v

        return str(jax.make_jaxpr(f)([p._value for p in params], tokens))

    text = trace()
    assert "eva" not in text
    with monkeypatch.context() as mp:
        mp.setattr(models.LlamaForCausalLM, "__init__", parents_init)
        assert trace() == text
