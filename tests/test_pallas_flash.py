"""Pallas flash attention numerics vs the dense reference (interpret mode on
CPU — the kernel itself, not the XLA fallback; mirrors the reference's
flash-attn tolerance tests, SURVEY.md §7 hard part (d))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.flash_attention import _reference_attention
from paddle_tpu.ops.pallas_flash import flash_attention


def _qkv(B=1, S=256, H=2, D=128, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal)
    ref = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    q, k, v = _qkv(S=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        scale = np.abs(np.asarray(b)).max() + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=2e-4, atol=2e-5)


def test_multi_block_sequence():
    # several q and kv blocks (S > block size) exercises the online-softmax
    # accumulation across grid steps
    q, k, v = _qkv(S=512, H=1)
    out = flash_attention(q, k, v, True)
    ref = _reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_bf16_inputs():
    q, k, v = _qkv(S=128, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, True)
    ref = _reference_attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_under_jit():
    q, k, v = _qkv(S=128)
    jitted = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))
    np.testing.assert_allclose(
        np.asarray(jitted(q, k, v)),
        np.asarray(flash_attention(q, k, v, True)), rtol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_forward_matches_reference(causal):
    # 4 query heads per KV head, consumed via BlockSpec index maps
    rng = np.random.default_rng(3)
    B, S, H, Hkv, D = 1, 256, 4, 1, 128
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    out = flash_attention(q, k, v, causal)
    ref = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_grads_match_reference(causal):
    rng = np.random.default_rng(4)
    B, S, H, Hkv, D = 1, 128, 4, 2, 128
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        scale = np.abs(np.asarray(b)).max() + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=2e-4, atol=2e-5)


class TestAutotuneCache:
    """N11 autotune-cache analog (ops/autotune.py)."""

    def test_candidates_respect_divisibility(self):
        from paddle_tpu.ops import autotune as at

        cands = at.candidates(256, 256, 128)
        assert (128, 128) in cands
        assert all(256 % bq == 0 and 256 % bk == 0 for bq, bk in cands)
        assert at.candidates(100, 100, 128) == [(128, 128)]  # fallback

    def test_key_is_batch_invariant(self, monkeypatch):
        """Block choice depends on (seq, heads, head_dim), not batch —
        a caller that halves its batch must keep hitting the cache."""
        from paddle_tpu.ops import autotune as at

        monkeypatch.setattr(at, "_memory", {})
        monkeypatch.setattr(at, "_loaded", True)  # no disk load
        at._memory[at._key((8, 2048, 8, 128), (8, 2048, 8, 128),
                           "bfloat16", True)] = (256, 256)
        for b in (4, 2, 1):  # the OOM ladder
            assert at.cached_flash_blocks(
                (b, 2048, 8, 128), (b, 2048, 8, 128),
                "bfloat16", True) == (256, 256)
        # different seq is still a different key
        assert at.cached_flash_blocks(
            (8, 1024, 8, 128), (8, 1024, 8, 128), "bfloat16", True) is None

    def test_committed_old_format_keys_migrate_on_load(self, tmp_path,
                                                       monkeypatch):
        """Pre-migration AUTOTUNE.json keys carried the batch dim; they
        must keep hitting after the key change."""
        import json

        from paddle_tpu.ops import autotune as at

        committed = tmp_path / "AUTOTUNE.json"
        old_key = ("flash|(8, 2048, 8, 128)|(8, 2048, 8, 128)|bfloat16|"
                   "True|" + __import__("jax").devices()[0].device_kind)
        committed.write_text(json.dumps({old_key: [512, 256]}))
        monkeypatch.setattr(at, "_COMMITTED_PATH", str(committed))
        monkeypatch.setattr(at, "_CACHE_PATH", str(tmp_path / "rt.json"))
        monkeypatch.setattr(at, "_memory", {})
        monkeypatch.setattr(at, "_loaded", False)
        assert at.cached_flash_blocks((2, 2048, 8, 128), (2, 2048, 8, 128),
                                      "bfloat16", True) == (512, 256)

    def test_tune_persists_and_hits(self, tmp_path, monkeypatch):
        from paddle_tpu.ops import autotune as at

        monkeypatch.setattr(at, "_CACHE_PATH",
                            str(tmp_path / "autotune.json"))
        monkeypatch.setattr(at, "_memory", {})
        monkeypatch.setattr(at, "_loaded", False)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((1, 256, 2, 128)).astype("float32"))
        k = jnp.asarray(rng.standard_normal((1, 256, 2, 128)).astype("float32"))
        blocks = at.tune_flash_blocks(q, k, k, causal=False, iters=1)
        assert blocks in at.candidates(256, 256, 128)
        # memoized: second call returns instantly from memory
        assert at.tune_flash_blocks(q, k, k, causal=False) == blocks
        # persisted: a fresh load sees it
        monkeypatch.setattr(at, "_memory", {})
        monkeypatch.setattr(at, "_loaded", False)
        assert at.cached_flash_blocks(q.shape, k.shape, str(q.dtype),
                                      False) == blocks

    def test_committed_results_consumed_at_call_time(self, tmp_path,
                                                     monkeypatch):
        # VERDICT r4 item #2: the on-chip sweep writes AUTOTUNE.json and
        # cached_flash_blocks() must consult it with no flag set
        from paddle_tpu.ops import autotune as at

        monkeypatch.setattr(at, "_CACHE_PATH",
                            str(tmp_path / "runtime.json"))
        monkeypatch.setattr(at, "_COMMITTED_PATH",
                            str(tmp_path / "AUTOTUNE.json"))
        monkeypatch.setattr(at, "_memory", {})
        monkeypatch.setattr(at, "_loaded", False)
        key = at.record((8, 2048, 8, 128), (8, 2048, 8, 128), "bfloat16",
                        True, (256, 512), committed=True)
        assert "flash|" in key
        # fresh process simulation: only the committed file survives
        (tmp_path / "runtime.json").unlink()
        monkeypatch.setattr(at, "_memory", {})
        monkeypatch.setattr(at, "_loaded", False)
        assert at.cached_flash_blocks((8, 2048, 8, 128), (8, 2048, 8, 128),
                                      "bfloat16", True) == (256, 512)


@pytest.mark.parametrize("causal", [False, True])
def test_head_dim_64(causal):
    # BERT/GPT-2 head size: Mosaic-legal because the D block equals the
    # full array dim (use_flash admits 64 alongside multiples of 128)
    q, k, v = _qkv(D=64)
    out = flash_attention(q, k, v, causal)
    ref = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda q, k, v: (flash_attention(q, k, v, causal)
                                  .astype(jnp.float32) ** 2).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: (_reference_attention(q, k, v, causal)
                                   .astype(jnp.float32) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_use_flash_head_dim_gate():
    from paddle_tpu.ops.flash_attention import use_flash

    # gate decisions are backend-independent except the final tpu check;
    # assert the head_dim arm directly
    shapes = {64: True, 128: True, 256: True, 96: False, 192: False}
    for hd, legal in shapes.items():
        got = use_flash((2, 2048, 4, hd), None)
        # on CPU use_flash is always False; test the documented rule by
        # checking which shapes short-circuit BEFORE the backend check
        if not legal:
            assert got is False
