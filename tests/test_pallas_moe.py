"""The routed experts' decode-size grouped matmul (ISSUE 51:
``ops/pallas_moe.py``), in interpret mode on the CPU against
``jax.lax.ragged_dot`` AND a float32 numpy loop over experts; the rule of
``parallel.moe._grouped_swiglu`` at the shapes of the four cells with routed
experts; and ``moe_streamed`` on the launches of a tiny engine whose expert
layers are made to take the kernel.  float32 operands unless a case says
otherwise: the kernel's sums run in another order than XLA's, so results
agree to rounding and not bit for bit."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import pallas_moe
from paddle_tpu.parallel import moe

H, F, E = 128, 128, 8       # whole lane tiles


@pytest.fixture
def streamed(monkeypatch):
    """The rule as a TPU backend would read it (the kernel itself stays in
    interpret mode: ``pallas_moe._interpret`` asks the backend again)."""
    monkeypatch.setattr(pallas_moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe, "last_path", None)


def weights(rng, n=E, dtype=jnp.float32):
    return (jnp.asarray(rng.standard_normal((n, H, 2 * F)) * H ** -0.5, dtype),
            jnp.asarray(rng.standard_normal((n, F, H)) * F ** -0.5, dtype))


def expert_np(x, wgu, wd, limit):
    """``E(x)`` of one expert, float32 numpy."""
    h = x @ wgu
    gate, up = h[:, :F], h[:, F:]
    if limit is not None:
        gate, up = np.minimum(gate, limit), np.clip(up, -limit, limit)
    return (gate / (1 + np.exp(-gate)) * up) @ wd


def dropless_np(x, ids, w, wgu, wd, held, limit):
    """The float32 loop over experts ``dropless_experts`` stands for."""
    x, w, wgu, wd = (np.asarray(a, np.float32) for a in (x, w, wgu, wd))
    out = np.zeros_like(x)
    for local, e in enumerate(held):
        t, j = np.nonzero(np.asarray(ids) == e)
        if len(t):
            np.add.at(out, t, w[t, j, None]
                      * expert_np(x[t], wgu[local], wd[local], limit))
    return out


def routed(rng, T, k, experts, n=E):
    """``ids`` [T, k] over ``experts`` (distinct a token), weights."""
    ids = np.stack([rng.permutation(experts)[:k] for _ in range(T)])
    w = rng.random((T, k)).astype(np.float32) + 0.1
    return jnp.asarray(ids, jnp.int32), jnp.asarray(w / w.sum(1, keepdims=True))


CASES = {
    # name: (T, k, experts a token may reach, held, limit, row-block bytes)
    "balanced": (32, 4, range(E), None, None, None),
    "all_pairs_on_two_experts": (24, 2, (2, 5), None, None, None),
    "no_row_first_middle_last": (16, 3, (1, 2, 4, 6), None, None, None),
    "group_straddles_a_row_block": (40, 3, (0, 1, 2), None, None,
                                    32 * H * 4),
    "limit": (32, 4, range(E), None, 0.5, None),
    "one_decode_row_k4": (1, 4, range(E), None, None, None),
    "one_decode_row_k8": (1, 8, range(E), None, None, None),
    "held_share": (24, 4, range(E), (1, 2, 5, 6), None, None),
    "held_share_limit": (24, 4, range(E), (0, 3, 7), 0.7, None),
    "held_share_none_routed": (8, 2, (0, 1, 2, 3), (5, 6), None, None),
    # T k above ONE_PASS_PAIRS: ``_held_pairs_in_chunks``; two passes where
    # the held expert draws more than twice its uniform share (600 pairs,
    # 512 a pass)
    "held_in_chunks": (300, 4, range(E), (2, 3), None, None),
    "held_in_chunks_two_passes": (600, 2, (0, 1), (0,), 0.9, None),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_agrees_with_ragged_dot_and_the_loop(case, streamed,
                                                        monkeypatch):
    """``dropless_experts`` with its grouped products through the kernel,
    the rows no group owns filled with NaN on the way in: finite, equal to
    ``ragged_dot``'s and to the float32 loop over experts."""
    T, k, reach, held, limit, block_bytes = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    if block_bytes:
        monkeypatch.setattr(pallas_moe, "ROW_BLOCK_BYTES", block_bytes)
    # the kernel whatever the rows a group (a pass of the bounded path holds
    # hundreds an expert: ``ragged_dot``'s by the rule)
    monkeypatch.setattr(pallas_moe, "STREAM_ROWS_PER_EXPERT", 1 << 20)
    n_held = E if held is None else len(held)
    wgu, wd = weights(rng, n_held)
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    ids, w = routed(rng, T, k, list(reach))
    args = (x, ids, w, wgu, wd, E)

    real, paths = moe._grouped_swiglu, []

    def poisoned(rows, wgu, wd, sizes, limit=None):
        owned = jnp.arange(rows.shape[0])[:, None] < jnp.sum(sizes)
        out = real(jnp.where(owned, rows, jnp.nan), wgu, wd, sizes, limit)
        paths.append(moe.last_path)
        return out

    monkeypatch.setattr(moe, "_grouped_swiglu", poisoned)
    got, load = moe.dropless_experts(*args, held=held, limit=limit)
    assert paths and set(paths) == {"pallas"}
    monkeypatch.setattr(moe, "_grouped_swiglu", real)
    monkeypatch.setattr(pallas_moe, "_on_tpu", lambda: False)
    want, want_load = moe.dropless_experts(*args, held=held, limit=limit)
    assert moe.last_path == "xla"

    got = np.asarray(got)
    assert got.shape == (T, H) and np.isfinite(got).all()
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)
    loop = dropless_np(x, ids, w, wgu, wd,
                       range(E) if held is None else held, limit)
    np.testing.assert_allclose(got, loop, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sizes,tm,block", [
    ([5, 0, 17, 3, 0, 20, 9, 0], 16, 32),       # three blocks, straddled
    ([0, 0, 0, 0, 0, 0, 0, 64], 32, 64),        # the last group owns all
    ([64, 0, 0, 0, 0, 0, 0, 0], 8, 16),         # one group over four blocks
    ([1, 1, 1, 1, 1, 1, 1, 1], 8, 64),          # a row a group, a tail
    ([0, 0, 0, 0, 0, 0, 0, 0], 16, 32),         # no row at all
    ([7, 9, 0, 0, 30, 0, 2, 3], 64, 64),        # one pass covers a block
])
def test_one_product_against_ragged_dot(sizes, tm, block):
    """``grouped_matmul`` alone at explicit tiles, float32 and bf16: the
    rows a group owns are ``ragged_dot``'s (bf16: bit for bit, one float32
    sum rounded once); the visit list names each touched group once a row
    block it has rows in, in order, and repeats its last entry after."""
    rng = np.random.default_rng(sum(sizes) + tm)
    M, N = 64, 256
    rows = jnp.asarray(rng.standard_normal((M, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, H, N)) * H ** -0.5, jnp.float32)
    sz = jnp.asarray(sizes, jnp.int32)
    n = sum(sizes)
    got = pallas_moe.grouped_matmul(rows, w, sz, tm=tm, tn=128, block=block)
    assert got.shape == (M, N) and got.dtype == rows.dtype
    np.testing.assert_allclose(np.asarray(got)[:n],
                               np.asarray(jax.lax.ragged_dot(rows, w, sz))[:n],
                               rtol=2e-5, atol=2e-5)
    if tm % 16 == 0:
        rb, wb = rows.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        got = pallas_moe.grouped_matmul(rb, wb, sz, tm=tm, block=block)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[:n],
            np.asarray(jax.lax.ragged_dot(rb, wb, sz), np.float32)[:n],
            rtol=2e-2, atol=2e-2)

    group, blk, lo, hi, count = (np.asarray(a) for a in pallas_moe.visit_list(
        sz, block, M // block))
    assert len(group) == E + M // block - 1
    ends = np.cumsum(sizes)
    want = [(g, b) for g in range(E) for b in range(M // block)
            if sizes[g] and ends[g] - sizes[g] < (b + 1) * block
            and ends[g] > b * block]
    assert int(count[0]) == len(want)
    assert list(zip(group, blk))[:len(want)] == want
    if want:
        assert set(zip(group[len(want):], blk[len(want):])) <= {want[-1]}
        covered = sum(hi[:len(want)] - lo[:len(want)])
        assert covered == n


def test_tiles_follow_the_shapes():
    bf16 = jnp.bfloat16
    # the latent cell: every row in one block, 6.3 MB weight blocks
    assert pallas_moe.row_block(512, 2048, bf16) == 512
    assert pallas_moe.column_tile(2048, 3072, bf16) == 1536
    assert pallas_moe.column_tile(1536, 2048, bf16) == 2048
    # the delta cell's 7,168 wide rows: 256 of 1,024 a block (3.7 MB)
    assert pallas_moe.row_block(1024, 7168, bf16) == 256
    assert pallas_moe.column_tile(7168, 4096, bf16) == 512
    # a decode row alone: one pass
    assert pallas_moe.row_block(4, 2048, bf16) == pallas_moe.PASS_ROWS
    with pytest.raises(ValueError, match="do not divide"):
        pallas_moe.grouped_matmul(jnp.zeros((32, 128)), jnp.zeros((2, 128, 256)),
                                  jnp.zeros((2,), jnp.int32), tn=384)


def shapes(rows, n_held, h, f, dtype=jnp.bfloat16):
    s = jax.ShapeDtypeStruct
    return (s((rows, h), dtype), s((n_held, h, 2 * f), dtype),
            s((n_held, f, h), dtype))


DECODE = {      # the decode program's rows, experts held, H, F
    "glm-4.7-flash": (128 * 4, 64, 2048, 1536),
    "command-a-plus-05-2026": (32 * 8, 16, 4096, 4096),
    "gigachat3.5-432b-a28b": (128 * 8, 16, 7168, 2048),
    "xing4.0-29b-a4b": (16 * 4, 64, 3584, 1024),
}


@pytest.mark.parametrize("cell", DECODE)
def test_the_rule_picks_the_kernel_at_decode_shapes(cell, streamed):
    assert pallas_moe.streams(*shapes(*DECODE[cell]))


@pytest.mark.parametrize("tokens,kernel", [
    (2048, True), (4096, True),     # the prefill buckets: 128, 256 an expert
    (16384, True),                  # 1,024 rows an expert: as far as Step 0
    (32768, False),                 # measured; above it ``ragged_dot``
])
def test_the_rule_ends_where_the_measurement_did(tokens, kernel, streamed):
    """ISSUE 51 expected a crossing between 64 and 256 rows an expert and
    ``ragged_dot`` at ``xing``'s 2,048 to 4,096 buckets; Step 0 found the
    kernel 1.5 to 2.5 times ahead at every size up to 1,024 rows an expert
    (``pallas_moe.STREAM_ROWS_PER_EXPERT``), so the rule ends there."""
    assert pallas_moe.streams(*shapes(tokens * 4, 64, 3584, 1024)) == kernel
    assert pallas_moe.streams(*shapes(tokens * 4, 64, 2048, 1536)) == kernel
    # a pass of the bounded path: the chunk's rows over the experts held
    assert pallas_moe.streams(*shapes(tokens, 16, 4096, 4096)) == kernel


def test_the_rule_reads_the_backend_the_dtype_and_the_tiles(streamed,
                                                            monkeypatch):
    latent = DECODE["glm-4.7-flash"]
    assert pallas_moe.streams(*shapes(*latent, dtype=jnp.float32))
    assert not pallas_moe.streams(*shapes(*latent, dtype=jnp.float16))
    assert not pallas_moe.streams(*shapes(512, 64, 2048, 1536 + 64))
    assert not pallas_moe.streams(*shapes(512, 64, 2048 + 64, 1536))
    rows, wgu, wd = shapes(*latent)
    mixed = jax.ShapeDtypeStruct(rows.shape, jnp.float32)
    assert not pallas_moe.streams(mixed, wgu, wd)
    per = pallas_moe.STREAM_ROWS_PER_EXPERT
    assert pallas_moe.streams(*shapes(64 * per, 64, 2048, 1536))
    assert not pallas_moe.streams(*shapes(64 * per + 64, 64, 2048, 1536))
    monkeypatch.setattr(pallas_moe, "_mesh_mp", lambda: 4)
    assert not pallas_moe.streams(*shapes(*latent))
    monkeypatch.setattr(pallas_moe, "_mesh_mp", lambda: 1)
    monkeypatch.setattr(pallas_moe, "_on_tpu", lambda: False)
    assert not pallas_moe.streams(*shapes(*latent))


@pytest.mark.parametrize("pin", ["cpu", "kill_switch", "flag"])
def test_ragged_dot_is_pinned_off_the_tpu_and_by_the_kill_switch(
        pin, monkeypatch):
    from paddle_tpu.core import flags

    if pin != "cpu":
        monkeypatch.setattr(pallas_moe, "_on_tpu", lambda: True)
    if pin == "kill_switch":
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    if pin == "flag":
        monkeypatch.setattr(flags, "flag",
                            lambda name: name == "disable_pallas_kernels")
    monkeypatch.setattr(pallas_moe, "grouped_matmul", lambda *a, **k: 1 / 0)
    rng = np.random.default_rng(3)
    wgu, wd = weights(rng)
    x = jnp.asarray(rng.standard_normal((8, H)), jnp.float32)
    ids, w = routed(rng, 8, 2, list(range(E)))
    out, _ = moe.dropless_experts(x, ids, w, wgu, wd, E)
    assert moe.last_path == "xla" and out.shape == (8, H)


# --- the launch says that it engaged ------------------------------------------

TINY = dict(vocab_size=320, hidden_size=128, intermediate_size=128,
            moe_intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=256, rms_norm_eps=1e-5,
            rope_theta=10000.0, tie_word_embeddings=False, q_lora_rank=32,
            kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=20, n_routed_experts=8, n_shared_experts=1,
            num_experts_per_tok=2, routed_scaling_factor=1.8,
            norm_topk_prob=True, first_k_dense_replace=1,
            check={"margin_eps": 1e-5, "max_left_out_share": 0.002})


def fetches_of(monkeypatch, crossing):
    """The ``engine.fetch`` integers of a prompt of 10 tokens and three
    decode steps through a tiny engine whose rule reads a TPU backend and
    ``crossing`` rows an expert; the tokens; the registry's text."""
    from benchmarks import harness
    from paddle_tpu.serving import (EngineConfig, EngineCore, SchedulerConfig)
    from paddle_tpu.serving.request import SamplingParams

    monkeypatch.setattr(pallas_moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(pallas_moe, "STREAM_ROWS_PER_EXPERT", crossing)
    model = harness.load_module("models", "glm_moe_mla").build(
        TINY, 7, dtype="float32")
    eng = EngineCore(model, config=EngineConfig(
        num_blocks=64, block_size=4, dtype=jnp.float32, prefix_cache=False,
        scheduler=SchedulerConfig(max_num_seqs=8)))
    seen = []
    phase = eng.tracer.phase

    def spy(name, *a, **kw):
        if name == "engine.fetch":
            seen.append(dict(kw))
        return phase(name, *a, **kw)

    monkeypatch.setattr(eng.tracer, "phase", spy)
    prompt = np.random.default_rng(2).integers(1, 320, 10).tolist()
    req = eng.add_request(prompt, SamplingParams(max_new_tokens=4,
                                                 temperature=0.0))
    for _ in range(40):
        if req.finished:
            break
        eng.step()
    assert req.finished
    return seen, list(req.output_tokens), \
        eng.metrics.registry.prometheus_text()


def series(text, name):
    return [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith(name)]


def test_a_launch_says_whether_its_experts_streamed(monkeypatch):
    """Crossing 1 row an expert: the decode program (1 row x 2 = 2 pairs
    over 8 experts, the bucket's padding with it) takes the kernel, the
    prefill of a 16 bucket (32 pairs: 4 an expert) ``ragged_dot``; with
    the rule reading no TPU nothing streams, and the tokens are the
    same."""
    seen, toks, text = fetches_of(monkeypatch, crossing=1)
    decode = [f for f in seen if f["moe_decode"]]
    prefill = [f for f in seen if not f["moe_decode"]]
    assert decode and prefill
    assert all(f["moe_streamed"] == 1 for f in decode)
    assert all(f["moe_streamed"] == 0 for f in prefill)
    assert series(text, "serving_moe_streamed_launches_total") \
        == [float(len(decode))]

    with monkeypatch.context() as mp:
        seen, plain, text = fetches_of(mp, crossing=0)
    assert seen and all(f["moe_streamed"] == 0 for f in seen)
    assert series(text, "serving_moe_streamed_launches_total") == [0.0]
    assert plain == toks
