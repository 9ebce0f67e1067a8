"""What the two files of chunk-summarised-attention tests share (ISSUE 45:
``test_zzzzzzzzzzzzzzzzzzzzz_eva.py``, the reference against a naive form,
declarations, the layer's paths against the reference;
``test_zzzzzzzzzzzzzzzzzzzzz_eva_engine.py``, rows of mixed lengths, reuse,
preemption, counters, and faults planted in the program): the tiny
configuration (W = 32, C = 16, two heads), ONE model, a driver that serves
a request and keeps every launch's logits, and a NAIVE all-pairs form of
the equations in numpy float64 that can plant the four faults.  Two files
so that two test workers share them (``--dist loadfile``)."""

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks import harness

TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=512,
            rms_norm_eps=1e-5, rope_theta=10000.0, window_size=32,
            chunk_size=16, num_pred_heads=1, num_pred_heads_held=2)
ATOL, RMS_REL = 1e-4, 1e-4      # float32 against float32: rounding only
FAULTS = ("early", "swap", "sliding", "rotate_after")


@pytest.fixture(scope="module")
def builder():
    return harness.load_module("models", "eva_dense")


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("reference", "eva_decoder")


@pytest.fixture(scope="module")
def model(builder):
    m = builder.build(TINY, 7, dtype="float32")
    rng = np.random.default_rng(1)
    for _, p in m.named_parameters():   # offsets that are not 0: (1 + w)
        if len(p.shape) == 1:
            p._value = jnp.asarray(rng.normal(0, 0.1, p.shape), jnp.float32)
    return m


def make_engine(model, **kw):
    from paddle_tpu.serving import EngineConfig, EngineCore, SchedulerConfig

    sched = kw.pop("scheduler", None) or SchedulerConfig(max_num_seqs=4)
    cfg = dict(num_blocks=64, block_size=16, dtype=jnp.float32,
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


def served_logits(rows, steps):
    """The last ``steps + 1`` launches' logits: the prompt's last position
    and every decode step."""
    got = [l if l.ndim == 1 else l[0] for _, l in rows]
    return np.stack(got[-(steps + 1):])


def forward(model, ids):
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    with paddle.no_grad():
        return np.asarray(model(Tensor(jnp.asarray([ids])))._value[0])


# --- the equations, all pairs, numpy float64 ----------------------------------------

def _softmax(x, axis):
    e = np.exp(x - x.max(axis, keepdims=True))
    return e / e.sum(axis, keepdims=True)


def naive_logits(weights, m, ids, fault=None):
    """A full forward pass with every (query, key) and (query, chunk) pair
    scored and masked: no windows walked, no blocks, float64.  ``fault``
    plants one of :data:`FAULTS`."""
    f = lambda a: np.asarray(a, np.float64)     # noqa: E731
    heads = m["num_attention_heads"]
    d = m["hidden_size"] // heads
    W, C, eps = m["window_size"], m["chunk_size"], m["rms_norm_eps"]
    N = len(ids)
    t = np.arange(N)
    inv = 1.0 / (m["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float32) / d))
    inv = inv.astype(np.float32).astype(np.float64)

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1 + f(w))

    def rot(x, pos):                            # [n, heads, d] at pos [n]
        ang = (pos.astype(np.float32)[:, None]
               * inv.astype(np.float32)[None]).astype(np.float64)
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        a, b = x[..., :d // 2], x[..., d // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    x = f(weights["embed"])[np.asarray(ids)]
    n_chunks = N // C
    chunk = np.arange(n_chunks)
    for lw in weights["layers"]:
        n = norm(x, lw["norm1"])
        q = rot((n @ f(lw["q"])).reshape(N, heads, d), t)
        k_raw = (n @ f(lw["k"])).reshape(N, heads, d)
        k = rot(k_raw, t)
        v = (n @ f(lw["v"])).reshape(N, heads, d)
        mu, phi = f(lw["mu"]), f(lw["phi"])
        if fault == "swap":
            mu, phi = phi, mu
        src = k_raw if fault == "rotate_after" else k
        kc = src[:n_chunks * C].reshape(n_chunks, C, heads, d)
        vc = v[:n_chunks * C].reshape(n_chunks, C, heads, d)
        wk = _softmax(np.einsum("nchd,hd->nch", kc, mu), 1)
        wv = _softmax(np.einsum("nchd,hd->nch", kc, phi), 1)
        kbar = np.einsum("nch,nchd->nhd", wk, kc)
        vbar = np.einsum("nch,nchd->nhd", wv, vc)
        if fault == "rotate_after":
            kbar = rot(kbar, chunk * C)
        local = (t[None] // W == t[:, None] // W) & (t[None] <= t[:, None])
        if fault == "sliding":
            local = (t[:, None] - t[None] >= 0) & (t[:, None] - t[None] < W)
        closed = t[:, None] // W + (1 if fault == "early" else 0)
        remote = (chunk[None] * C) // W < closed
        s = np.concatenate([
            np.where(local[None], np.einsum("thd,shd->hts", q, k), -np.inf),
            np.where(remote[None], np.einsum("thd,chd->htc", q, kbar),
                     -np.inf)], -1) / np.sqrt(d)
        p = _softmax(s, -1)
        o = np.einsum("hts,shd->thd", p[..., :N], v) \
            + np.einsum("htc,chd->thd", p[..., N:], vbar)
        x = x + o.reshape(N, heads * d) @ f(lw["o"])
        n = norm(x, lw["norm2"])
        g = n @ f(lw["gate"])
        x = x + (g / (1 + np.exp(-g)) * (n @ f(lw["up"]))) @ f(lw["down"])
    return norm(x, weights["norm"]) @ f(weights["head"])[:, :m["vocab_size"]]
