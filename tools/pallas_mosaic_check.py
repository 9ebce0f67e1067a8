"""Mosaic verdict: does each Pallas kernel compile for the chip, and right?

Off the chip the kernels only ever run in interpret mode, and Mosaic
rejects kernels that interpret fine.  This tool compiles every kernel of
``paddle_tpu/ops`` (and the fused sampling epilogue, plain XLA) with the
real backend at the shapes the Llama-3-8B serving path and the benchmark's
cells launch, compares each against its XLA reference, and records the
compiler's own words where it refuses.  Times are host-clock information,
not a benchmark.

    chiprun -- python tools/pallas_mosaic_check.py [--beside FILE] [check ...]

One JSON line per check on stdout; the table lands in
``chiprun_out/PALLAS_VERDICT.json`` (copy it over the committed record).
``--beside FILE`` names the table another commit's run of this tool wrote
in the same call: each check then carries that run's milliseconds too, as
``beside_ms``.  Exits non-zero without a TPU, and when any check fails.
"""

from __future__ import annotations

import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _HERE)

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.utils.compile_cache import configure_compile_cache

OUT_DIR = os.path.join(_HERE, "chiprun_out")
D = 128           # head dim
BS = 16           # KV block size
NB = 2048         # pool blocks (32k tokens, the one-chip smoke's pool)


def _bench(fn, *args, iters=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / iters * 1e3, 3)


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _rand(rng, shape, dtype=jnp.bfloat16):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# --- flash attention (training / one-shot prefill shapes) -------------------

def _xla_attn(q, k, v, causal):
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def flash_check(causal, hkv=8, d=D, grad=False):
    def run():
        from paddle_tpu.ops import pallas_flash

        rng = np.random.default_rng(0)
        q = _rand(rng, (4, 2048, 8, d))
        k = _rand(rng, (4, 2048, hkv, d))
        v = _rand(rng, (4, 2048, hkv, d))

        def wrap(attn):
            if not grad:
                return jax.jit(lambda q, k, v: attn(q, k, v, causal))
            return jax.jit(jax.grad(
                lambda q, k, v: attn(q, k, v, causal)
                .astype(jnp.float32).sum(), argnums=(0, 1, 2)))

        f = wrap(pallas_flash.flash_attention)
        out, ref = f(q, k, v), wrap(_xla_attn)(q, k, v)
        err = (max(_err(a, b) for a, b in zip(out, ref)) if grad
               else _err(out, ref))
        # bf16 attention tolerance; gradients accumulate more error
        return {"ok": err < (0.5 if grad else 0.15), "max_err": err,
                "pallas_ms": _bench(f, q, k, v)}
    return run


# --- paged decode (one token per row over the block pool) -------------------

def paged_decode_check(h, hkv, pool_dtype=jnp.bfloat16, batch=8, width=64,
                       block=BS, ring=False):
    """``ring``: the pool is ``batch`` rings of ``width`` pages, a row's
    table its own pages in order, clamped to the last one it has written
    (``ops.window_attention.ring_decode_attention``)."""
    def run():
        from paddle_tpu.ops import pallas_paged

        rng = np.random.default_rng(1)
        nb = batch * width if ring else NB
        kc = _rand(rng, (nb, block, hkv, D), pool_dtype)
        vc = _rand(rng, (nb, block, hkv, D), pool_dtype)
        q = _rand(rng, (batch, h, D))
        sl = rng.integers(1, width * block, (batch,))
        if ring:
            bt = np.arange(batch)[:, None] * width + np.minimum(
                np.arange(width)[None, :], ((sl - 1) // block)[:, None])
        else:
            bt = rng.integers(1, NB, (batch, width))
        bt, sl = jnp.asarray(bt, jnp.int32), jnp.asarray(sl, jnp.int32)
        f = jax.jit(pallas_paged.paged_attention_decode)
        out = f(q, kc, vc, bt, sl)
        ref = jax.jit(pallas_paged.decode_oracle)(q, kc, vc, bt, sl)
        err = _err(out, ref)
        return {"ok": err < 0.05, "max_err": err,
                "pallas_ms": _bench(f, q, kc, vc, bt, sl)}
    return run


# --- ragged packed step (prefill chunks + decode rows in one launch) --------

def _ragged_inputs(rng, tokens, width, h):
    """One prefill chunk filling half the bucket plus decode rows, packed;
    per-ROW tables are padded to one row per token as the engine does."""
    chunk = tokens // 2
    n_dec = min(8, tokens - chunk)
    tables = np.zeros((tokens, width), np.int32)
    lens = np.ones((tokens,), np.int32)
    seg = np.full((tokens,), min(n_dec + 1, tokens - 1), np.int32)
    pos = np.zeros((tokens,), np.int32)
    start = min(width * BS - chunk, 300)
    tables[0] = rng.integers(1, NB, width)
    lens[0] = start + chunk
    seg[:chunk] = 0
    pos[:chunk] = np.arange(start, start + chunk)
    for r in range(1, n_dec + 1):
        tables[r] = rng.integers(1, NB, width)
        lens[r] = int(rng.integers(1, width * BS))
        seg[chunk + r - 1] = r
        pos[chunk + r - 1] = lens[r] - 1
    q = _rand(rng, (tokens, h, D))
    return q, tuple(jnp.asarray(a) for a in (tables, lens, seg, pos))


def ragged_check(tokens, width, h=32, hkv=8, oracle=True):
    def run():
        from paddle_tpu.ops import ragged_paged

        rng = np.random.default_rng(2)
        kc, vc = _rand(rng, (NB, BS, hkv, D)), _rand(rng, (NB, BS, hkv, D))
        q, meta = _ragged_inputs(rng, tokens, width, h)
        f = jax.jit(ragged_paged._ragged_attention_kernel)
        out = f(q, kc, vc, *meta)
        info = {"finite": bool(jnp.isfinite(out.astype(jnp.float32)).all()),
                "smem_bytes": 4 * tokens * (max(width, 128) + 3),
                "pallas_ms": _bench(f, q, kc, vc, *meta, iters=3)}
        if oracle:   # the gather reference is [T, W*bs, Hkv, D] float32
            ref = jax.jit(ragged_paged.ragged_oracle)(q, kc, vc, *meta)
            info["max_err"] = _err(out, ref)
        info["ok"] = info["finite"] and info.get("max_err", 0.0) < 0.05
        return info
    return run


def prefetch_limit_check(tokens, width):
    """Past scalar memory the launch is refused by name before it reaches
    the compiler, which said on libtpu 0.0.34, for [2048, 128] and for
    [2048, 64] tables alike (SMEM pads a row to 128 words): "Ran out of
    memory in memory space smem. Used 1.02M of 1.00M smem"."""
    def run():
        try:
            ragged_check(tokens, width, oracle=False)()
        except ValueError as e:
            return {"ok": "scalar memory" in str(e), "refused": str(e)[:160]}
        return {"ok": False, "refused": None}
    return run


# --- fused sampling epilogue (XLA: full-vocabulary sort per row) ------------

def sampler_check(rows, vocab=128256):
    def run():
        from paddle_tpu.ops.sampling import sample_tokens

        rng = np.random.default_rng(3)
        logits = _rand(rng, (rows, vocab), jnp.float32)
        temps = jnp.asarray(np.where(np.arange(rows) % 4 == 3, 0.8, 0.0),
                            jnp.float32)
        top_ks = jnp.full((rows,), 40, jnp.int32)
        top_ps = jnp.full((rows,), 0.9, jnp.float32)
        keys = jnp.asarray(rng.integers(0, 2**31, (rows, 2)), jnp.uint32)
        f = jax.jit(sample_tokens)
        toks = np.asarray(f(logits, temps, top_ks, top_ps, keys))
        greedy = np.asarray(jnp.argmax(logits, axis=-1))
        g = np.asarray(temps) <= 0
        peak = jax.devices()[0].memory_stats().get("peak_bytes_in_use", 0)
        return {"ok": bool((toks[g] == greedy[g]).all()
                           and (toks >= 0).all() and (toks < vocab).all()),
                "xla_ms": _bench(f, logits, temps, top_ks, top_ps, keys,
                                 iters=3),
                "peak_hbm_gb_so_far": round(peak / 1e9, 2)}
    return run


# --- decode steps in place on a slot pool ------------------------------------

def _first_and_ms(step, pool0, iters=10):
    """``step(pool) -> (y, pool)`` with the pool donated, as a step program
    donates it: the first launch's results (kept: the pool is donated on)
    and the milliseconds a launch of ``iters`` more in a row."""
    f = jax.jit(step, donate_argnums=0)
    y, pool = f(jnp.array(pool0))
    first = (y, jnp.array(pool))
    jax.block_until_ready(first)
    t0 = time.perf_counter()
    for _ in range(iters):
        y, pool = f(pool)
    jax.block_until_ready((y, pool))
    return first, round((time.perf_counter() - t0) / iters * 1e3, 3)


# --- selective-scan decode step, in place on the slot pool -------------------

def ssm_state_step_check(rows=256, n=16, d=5120):
    """``pallas_ssm.state_step`` at AI21-Jamba2-3B's widths (state 16 x
    5,120 float32, 257 slots, every row on a scattered slot of its own)
    against gather -> ``selective_step`` -> scatter, the pool donated to
    both as a step program donates it; ``beside_ms`` is the XLA path's."""
    def run():
        from paddle_tpu.ops import pallas_ssm
        from paddle_tpu.ops.selective_scan import selective_step

        rng = np.random.default_rng(4)
        f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        x, Bm, Cm, A = f32(rows, d), f32(rows, n), f32(rows, n), -jnp.exp(
            f32(n, d))
        dt = jnp.asarray(rng.uniform(1e-3, 0.1, (rows, d)), jnp.float32)
        slots = jnp.asarray(rng.permutation(np.arange(1, rows + 1)),
                            jnp.int32)
        pool0 = f32(rows + 1, n, d)

        def kernel(pool):
            return pallas_ssm.state_step(x, dt, A, Bm, Cm, pool, slots)

        def xla(pool):
            y, h = selective_step(x, dt, A, Bm, Cm, pool[slots])
            return y, pool.at[slots].set(h)

        out, ms = _first_and_ms(kernel, pool0)
        ref, xla_ms = _first_and_ms(xla, pool0)
        err = max(_err(a, b) for a, b in zip(out, ref))
        return {"ok": err < 1e-5, "max_err": err, "pallas_ms": ms,
                "beside_ms": xla_ms}
    return run


# --- the routed experts' grouped matmul -------------------------------------

def moe_grouped_matmul_check(rows, experts, h, f, real=None, touched=None):
    """``pallas_moe.grouped_matmul`` for both products of one routed-expert
    layer (``[experts, h, 2 f]``, ``[experts, f, h]`` bf16, XLA's SwiGLU
    between them) against ``jax.lax.ragged_dot``: ``real`` of the ``rows``
    routed over ``touched`` experts (all of both by default), the rest a
    tail no group owns; ``beside_ms`` is ``ragged_dot``'s and ``floor_ms``
    the touched experts' weights once at 819 GB/s."""
    def run():
        from paddle_tpu.ops import pallas_moe

        rng = np.random.default_rng(51)
        n_real, n_touched = real or rows, touched or experts
        sizes = np.zeros(experts, np.int32)
        sizes[np.sort(rng.permutation(experts)[:n_touched])] = \
            rng.multinomial(n_real, np.ones(n_touched) / n_touched)
        n_touched = int(np.count_nonzero(sizes))
        sizes = jnp.asarray(sizes)
        x = _rand(rng, (rows, h))
        k1, k2 = jax.random.split(jax.random.PRNGKey(51))
        wgu = jax.random.normal(k1, (experts, h, 2 * f), jnp.bfloat16) \
            * h ** -0.5
        wd = jax.random.normal(k2, (experts, f, h), jnp.bfloat16) * f ** -0.5

        def layer(product):     # the weights are arguments, not constants
            def both(x, wgu, wd):
                g = product(x, wgu, sizes)
                return product(jax.nn.silu(g[:, :f]) * g[:, f:], wd, sizes)
            return jax.jit(both)

        kernel, xla = layer(pallas_moe.grouped_matmul), \
            layer(jax.lax.ragged_dot)
        err = _err(kernel(x, wgu, wd)[:n_real], xla(x, wgu, wd)[:n_real])
        return {"ok": err < 1e-2, "max_err": err,
                "pallas_ms": _bench(kernel, x, wgu, wd),
                "beside_ms": _bench(xla, x, wgu, wd),
                "floor_ms": round(n_touched * 3 * h * f * 2 / 819e9 * 1e3, 3)}
    return run


# --- gated delta-rule decode step, in place on the slot pool -----------------

def gdn_state_step_check(rows=128, hk=32, hv=64, d=128):
    """``pallas_gated_delta.state_step`` at GigaChat3.5-432B-A28B's widths
    (64 value heads on 32 key heads, states of 128 x 128 float32: 4.2 MB a
    row; the cell's 128 rows, every slot of 129 but the null one, scattered)
    against gather -> ``gated_delta_step`` -> scatter, the pool donated to
    both as a step program donates it; ``beside_ms`` is the XLA path's and
    ``floor_ms`` the state's two crossings at 819 GB/s."""
    def run():
        from paddle_tpu.ops import pallas_gated_delta
        from paddle_tpu.ops.gated_delta import gated_delta_step, l2_normalize

        rng = np.random.default_rng(5)
        f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        q = l2_normalize(f32(rows, hk, d)) / np.sqrt(d)
        k, v = l2_normalize(f32(rows, hk, d)), f32(rows, hv, d)
        log_alpha = -jnp.abs(f32(rows, hv)) * 0.1
        beta = jax.nn.sigmoid(f32(rows, hv))
        slots = jnp.asarray(rng.permutation(np.arange(1, rows + 1)),
                            jnp.int32)
        pool0 = f32(rows + 1, hv, d, d)
        rep = hv // hk

        def kernel(pool):
            return pallas_gated_delta.state_step(q, k, v, log_alpha, beta,
                                                 pool, slots)

        def xla(pool):
            o, s = gated_delta_step(jnp.repeat(q, rep, 1),
                                    jnp.repeat(k, rep, 1), v, log_alpha,
                                    beta, pool[slots])
            return o, pool.at[slots].set(s)

        out, ms = _first_and_ms(kernel, pool0)
        ref, xla_ms = _first_and_ms(xla, pool0)
        err = max(_err(a, b) for a, b in zip(out, ref))
        return {"ok": err < 1e-4, "max_err": err, "pallas_ms": ms,
                "beside_ms": xla_ms,
                "floor_ms": round(2 * rows * hv * d * d * 4 / 819e9 * 1e3, 3)}
    return run


# --- the expanded latent prefill (one-shot prefill of a latent-cache model) --

def flash_prefill_check(tokens, heads, nope, vd, rope=64, rank=512):
    """``latent_expanded_attention`` of one prompt of ``tokens`` tokens at
    a latent cell's head sizes, keys and values rebuilt from the latents
    and the kernel for the core, against its XLA form (float32 scores
    written whole): ``beside_ms`` is the XLA form's."""
    def run():
        from paddle_tpu.ops import paged_attention as ops

        rng = np.random.default_rng(5)
        q = _rand(rng, (1, tokens, heads, nope + rope))
        lat = _rand(rng, (1, tokens, rank + rope))
        w = tuple((_rand(rng, (heads, rank, d), jnp.float32)
                   / np.sqrt(rank)).astype(jnp.bfloat16) for d in (nope, vd))
        scale = 1.0 / np.sqrt(nope + rope)

        def form(use_pallas):
            return jax.jit(lambda q, lat, w, start: (
                ops.latent_expanded_attention(q, lat, w, rank, scale, start,
                                              use_pallas=use_pallas)))

        f, ref, start = form(True), form(False), jnp.int32(0)
        err = _err(f(q, lat, w, start), ref(q, lat, w, start))
        return {"ok": err < 0.05, "max_err": err,
                "pallas_ms": _bench(f, q, lat, w, start),
                "beside_ms": _bench(ref, q, lat, w, start)}
    return run


# --- the absorbed latent decode (one token a row over a latent pool) ---------

def latent_decode_check(rows, heads, nope, vd, lo, hi, width=512, rope=64,
                        rank=512, blocks=19200):
    """``latent_paged_decode_attention`` whole (both foldings and the core)
    at a latent cell's decode launch — the resident pool of 19,200 blocks
    of 16 tokens in whole lane tiles, ``rows`` rows of ``lo``..``hi``
    tokens behind tables of ``width`` entries — with the kernel that walks
    the pages, against its XLA form (the padded context gathered):
    ``beside_ms`` is the XLA form's."""
    def run():
        from paddle_tpu.ops import paged_attention as ops

        rng = np.random.default_rng(6)
        shape = ops.latent_pool_shape(blocks, BS, (1, rank + rope))
        pool = jnp.zeros(shape, jnp.bfloat16).at[..., :rank + rope].set(
            _rand(rng, shape[:2] + (rank + rope,)))
        q = _rand(rng, (rows, heads, nope + rope))
        w = tuple((_rand(rng, (heads, rank, d), jnp.float32)
                   / np.sqrt(rank)).astype(jnp.bfloat16) for d in (nope, vd))
        bt = jnp.asarray(rng.integers(1, blocks, (rows, width)), jnp.int32)
        sl = jnp.asarray(rng.integers(lo, hi, (rows,)), jnp.int32)
        scale = 1.0 / np.sqrt(nope + rope)

        def form(use_pallas):
            return jax.jit(lambda q, pool, w, bt, sl: (
                ops.latent_paged_decode_attention(
                    q, pool, w, bt, sl, rank, scale, use_pallas=use_pallas)))

        f, ref = form(True), form(False)
        err = _err(f(q, pool, w, bt, sl), ref(q, pool, w, bt, sl))
        return {"ok": err < 0.05, "max_err": err,
                "pallas_ms": _bench(f, q, pool, w, bt, sl),
                "beside_ms": _bench(ref, q, pool, w, bt, sl)}
    return run


CHECKS = [
    ("flash_fwd_causal=False", flash_check(False)),
    ("flash_fwd_causal=True", flash_check(True)),
    ("flash_bwd_causal=False", flash_check(False, grad=True)),
    ("flash_bwd_causal=True", flash_check(True, grad=True)),
    ("flash_fwd_gqa4", flash_check(True, hkv=2)),
    ("flash_bwd_gqa4", flash_check(True, hkv=2, grad=True)),
    ("flash_fwd_d64", flash_check(True, d=64)),
    # Llama-3-8B is GQA 32 query / 8 KV heads; under mp=4 a shard sees 8 / 2
    ("paged_decode_gqa_32q8kv", paged_decode_check(32, 8)),
    ("paged_decode_gqa_8q2kv_mp4_shard", paged_decode_check(8, 2)),
    ("paged_decode_mha_8q8kv", paged_decode_check(8, 8)),
    # multi-query: 20 query heads on ONE KV head (the hybrid's two
    # attention layers), at the backlog cell's 256 rows x 4,096 tokens
    ("paged_decode_mqa_20q1kv_rows256",
     paged_decode_check(20, 1, batch=256, width=256)),
    # EngineConfig.dtype=None: float32 pools under bf16 queries
    ("paged_decode_f32_pool_bf16_q",
     paged_decode_check(32, 8, pool_dtype=jnp.float32)),
    # the benchmark cells' launches (BENCHMARK.json): the widest bucket of
    # rows and the widest table each runs
    ("paged_decode_cell_mistral_32q8kv_rows32_w256",
     paged_decode_check(32, 8, batch=32, width=256)),
    ("paged_decode_cell_deepseek_32q32kv_rows64_w64",
     paged_decode_check(32, 32, batch=64, width=64)),
    ("paged_decode_cell_command_a_global_128q8kv_rows32_w512",
     paged_decode_check(128, 8, batch=32, width=512)),
    ("paged_decode_cell_command_a_ring_128q8kv_rows32_page256",
     paged_decode_check(128, 8, batch=32, width=16, block=256, ring=True)),
    ("ragged_T64_W64_32q8kv", ragged_check(64, 64)),
    ("ragged_T64_W64_8q2kv_mp4_shard", ragged_check(64, 64, h=8, hkv=2)),
    ("ragged_T256_W64_32q8kv", ragged_check(256, 64)),
    # scalar prefetch: tables[T, W] int32, one row per packed token, each
    # row padded to 128 words of a 1 MB SMEM
    ("ragged_T1024_W128_prefetch_512KB", ragged_check(1024, 128,
                                                      oracle=False)),
    ("ragged_T2048_W64_prefetch_refused", prefetch_limit_check(2048, 64)),
    ("ragged_T2048_W128_prefetch_refused", prefetch_limit_check(2048, 128)),
    ("sampler_rows8_vocab128256", sampler_check(8)),
    ("sampler_rows256_vocab128256", sampler_check(256)),
    ("sampler_rows2048_vocab128256", sampler_check(2048)),
    # the hybrid cell's decode launch: 256 rows, every slot but the null one
    ("ssm_state_step_256x16x5120", ssm_state_step_check()),
    # the delta-rule cell's decode launch: 128 rows, every slot but the null
    ("gdn_state_step_128x64x128x128", gdn_state_step_check()),
    # the routed experts of a decode launch: glm-4.7-flash's 128 rows x 4 over
    # 64 experts, gigachat3.5-432b-a28b's 1,024 static rows of which a step
    # routes about 54 to 9 of the 16 held experts, and xing4.0-29b-a4b's
    # 4,096-token prefill bucket (256 rows an expert)
    ("moe_grouped_matmul_cell_glm_rows512_64x2048x1536",
     moe_grouped_matmul_check(512, 64, 2048, 1536)),
    ("moe_grouped_matmul_cell_gigachat_rows1024_16x7168x2048_54real",
     moe_grouped_matmul_check(1024, 16, 7168, 2048, real=54, touched=9)),
    ("moe_grouped_matmul_cell_xing_prefill_rows16384_64x3584x1024",
     moe_grouped_matmul_check(16384, 64, 3584, 1024)),
    # the latent cells' one-shot prefill launches: xing4.0-29b-a4b's three
    # buckets (32 heads, 128 + 64 / 128) and glm-4.7-flash's widest (20
    # heads, 192 + 64 / 256)
    ("flash_prefill_cell_xing_32h_s4096_192_128",
     flash_prefill_check(4096, 32, 128, 128)),
    ("flash_prefill_cell_xing_32h_s2048_192_128",
     flash_prefill_check(2048, 32, 128, 128)),
    ("flash_prefill_cell_xing_32h_s1024_192_128",
     flash_prefill_check(1024, 32, 128, 128)),
    ("flash_prefill_cell_glm_20h_s1024_256_256",
     flash_prefill_check(1024, 20, 192, 256)),
    # the latent cells' decode launches: glm-4.7-flash's 128 rows of 0.5k-4k
    # tokens under 20 heads (192 + 64 / 256), xing4.0-29b-a4b's 8 rows of
    # 1k-4.1k under 32 heads (128 + 64 / 128)
    ("latent_decode_cell_glm_20h_rows128_w512",
     latent_decode_check(128, 20, 192, 256, 512, 4096)),
    ("latent_decode_cell_xing_32h_rows8_w512",
     latent_decode_check(8, 32, 128, 128, 1024, 4200)),
]


def main(argv) -> int:
    if jax.default_backend() != "tpu":
        print(f"pallas_mosaic_check: needs a TPU, found backend "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    cache = configure_compile_cache()
    import importlib.metadata as md

    dev = jax.devices()[0]
    results = {"device": dev.device_kind, "backend": jax.default_backend(),
               "versions": {p: md.version(p)
                            for p in ("jax", "jaxlib", "libtpu")},
               "compile_cache": cache, "checks": []}
    print(json.dumps({k: results[k] for k in ("device", "versions")}))
    args = argv[1:]
    beside = {}
    if "--beside" in args:
        at = args.index("--beside")
        with open(args[at + 1]) as f:
            beside = {c["name"]: c.get("pallas_ms", c.get("xla_ms"))
                      for c in json.load(f)["checks"]}
        del args[at:at + 2]
    only = set(args)
    errors = []
    for name, run in CHECKS:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            rec = run()
            rec["status"] = "pass" if rec.pop("ok") else "numerics"
        except Exception as e:  # the compiler's refusal IS the result
            text = f"{type(e).__name__}: {e}"
            errors.append(f"=== {name}\n{text}\n")
            rec = {"status": "refused",
                   "error": text if len(text) < 1600
                   else text[:600] + " … " + text[-900:]}
        if beside.get(name) is not None:
            rec["beside_ms"] = beside[name]
        rec = {"name": name, **rec,
               "seconds": round(time.perf_counter() - t0, 1)}
        results["checks"].append(rec)
        print(json.dumps(rec), flush=True)
    bad = [c["name"] for c in results["checks"] if c["status"] != "pass"]
    results["verdict"] = "pass" if not bad else f"{len(bad)} failing"
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "PALLAS_VERDICT.json"), "w") as f:
        json.dump(results, f, indent=1)
    if errors:
        with open(os.path.join(OUT_DIR, "mosaic_errors.txt"), "w") as f:
            f.write("\n".join(errors))
    print(json.dumps({"verdict": results["verdict"], "failing": bad}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
