"""Plain reference of the pre-norm decoder block with CHUNK-SUMMARISED
(EVA) attention: the ``evabyte`` block, as EvaByte publishes its sizes and
as Zheng, Yuan, Wang, Kong ("Efficient Attention via Control Variates",
ICLR 2023) and the model's released reference code give the attention its
form.

Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision("highest")``: the whole sequence at once, a
full forward pass, no cache, no ring, no rows in blocks of a pool, no
kernel.  It shares no code with the program under test.  It walks the
layers one at a time, casting ONE matrix of the served (bf16) weights to
float32 at a time; inside a layer every chunk of the sequence is
summarised, then the queries are taken a window at a time and within a
window in blocks of ``QUERY_BLOCK``, each block over its window's keys and
ALL the summaries under the two masks, so that 32,768 positions fit.

With ``d`` the head size, ``W = window_size``, ``C = chunk_size``, no
biases::

    x <- x + Attn(norm1(x));   x <- x + W_down(silu(W_gate n) * W_up n),  n = norm2(x)
    norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + w)

    q_t, k_t, v_t from norm1(x_t);  q_t, k_t rotated by position t
        (theta, all d dimensions, rotate-half pairing: i with i + d/2)
    chunk c = positions [C c, C c + C), whole chunks only:
        kbar_c = sum_j softmax_j(mu . k_j) k_j
        vbar_c = sum_j softmax_j(phi . k_j) v_j         (rotated keys)
    query t, window w = t // W:
        L_t = {s : s // W == w, s <= t},  R_t = {c : c < (W / C) w}
        o_t = (sum_L e^{q.k_s / sqrt d} v_s + sum_R e^{q.kbar_c / sqrt d} vbar_c)
            / (sum_L e^{q.k_s / sqrt d}     + sum_R e^{q.kbar_c / sqrt d})
    Attn = W_o concat_h o_t

    logits = norm_f(x) W_head[:, :vocab_size]      (head 0 of num_pred_heads)

What no key of ``config.json`` states is the configuration file's
``assumed``.  Weights arrive as plain arrays, ``[in, out]`` for every
matrix::

    {"embed": [V, H], "norm": [H], "head": [H, num_pred_heads * V],
     "layers": [{"norm1" [H], "q", "k", "v", "o" [H, H], "mu", "phi"
                 [heads, d], "norm2" [H], "gate", "up" [H, F], "down" [F, H]}]}
"""

from __future__ import annotations

from typing import Dict, Sequence

QUERY_BLOCK = 512


def _norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(jnp.float32))


def _rotate(x, theta):
    """``x`` ``[N, heads, d]`` at positions ``0..N-1``."""
    import jax.numpy as jnp
    import numpy as np

    n, d = x.shape[0], x.shape[-1]
    inv = (1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
           ).astype(np.float32)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def summaries(k, v, mu, phi, C: int):
    """``kbar, vbar`` ``[N // C, heads, d]`` of the whole chunks of ``k`` /
    ``v`` ``[N, heads, d]``."""
    import jax
    import jax.numpy as jnp

    n = k.shape[0] // C
    kc = k[:n * C].reshape(n, C, *k.shape[1:])
    vc = v[:n * C].reshape(n, C, *v.shape[1:])
    wk = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, mu), axis=1)
    wv = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, phi), axis=1)
    return (jnp.einsum("nch,nchd->nhd", wk, kc),
            jnp.einsum("nch,nchd->nhd", wv, vc))


def attention(q, k, v, kbar, vbar, W: int, C: int):
    """The equations over a whole sequence: ``q, k, v`` ``[N, heads, d]``
    (rotated), ``kbar, vbar`` ``[N // C, heads, d]``.  Returns ``[N,
    heads, d]``."""
    import jax
    import jax.numpy as jnp

    N, d = q.shape[0], q.shape[-1]
    scale = 1.0 / (d ** 0.5)
    n_chunks = kbar.shape[0]
    chunk_window = (jnp.arange(n_chunks) * C) // W
    out = []
    for first in range(0, N, W):                # a window
        last = min(first + W, N)
        kw, vw = k[first:last], v[first:last]
        s_pos = jnp.arange(first, last)
        for lo in range(first, last, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, last)
            t_pos = jnp.arange(lo, hi)
            loc = jnp.einsum("thd,shd->hts", q[lo:hi], kw) * scale
            loc = jnp.where((s_pos[None] <= t_pos[:, None])[None], loc,
                            -jnp.inf)
            parts, values = [loc], [vw]
            if n_chunks:
                rem = jnp.einsum("thd,chd->htc", q[lo:hi], kbar) * scale
                seen = chunk_window[None] < (t_pos // W)[:, None]
                parts.append(jnp.where(seen[None], rem, -jnp.inf))
                values.append(vbar)
            p = jax.nn.softmax(jnp.concatenate(parts, -1), -1)
            o = jnp.einsum("hts,shd->thd", p[..., :last - first], values[0])
            if n_chunks:
                o = o + jnp.einsum("htc,chd->thd", p[..., last - first:],
                                   values[1])
            out.append(o)
    return jnp.concatenate(out, 0)


def _layer(x, lw, m: Dict):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    heads = m["num_attention_heads"]
    d = m["hidden_size"] // heads
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    W, C = m["window_size"], m["chunk_size"]
    N = x.shape[0]
    n = _norm(x, lw["norm1"], eps)
    q = _rotate((n @ lw["q"].astype(f32)).reshape(N, heads, d), theta)
    k = _rotate((n @ lw["k"].astype(f32)).reshape(N, heads, d), theta)
    v = (n @ lw["v"].astype(f32)).reshape(N, heads, d)
    kbar, vbar = summaries(k, v, lw["mu"].astype(f32), lw["phi"].astype(f32),
                           C)
    o = attention(q, k, v, kbar, vbar, W, C).reshape(N, heads * d)
    x = x + o @ lw["o"].astype(f32)
    n = _norm(x, lw["norm2"], eps)
    g = jax.nn.silu(n @ lw["gate"].astype(f32)) * (n @ lw["up"].astype(f32))
    return x + g @ lw["down"].astype(f32)


def reference_logits(weights: Dict, m: Dict, ids: Sequence[int]):
    """Next-byte logits ``[len(ids), vocab_size]`` (float32) of a full
    forward pass over ``ids``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
        for lw in weights["layers"]:
            x = _layer(x, lw, m)
        x = _norm(x, weights["norm"], m["rms_norm_eps"])
        head = weights["head"][:, :m["vocab_size"]].astype(jnp.float32)
        return x @ head


def compare(got, want, atol: float, rms_rel: float) -> Dict:
    """Program logits against reference logits, both ``[rows, vocab]``:
    the largest difference of a logit against ``atol`` and the rms of the
    differences over the rms of the reference against ``rms_rel``."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    diff = got - want
    out = {"max_abs_diff": float(np.abs(diff).max()),
           "rms_rel": float(np.sqrt((diff ** 2).mean() / (want ** 2).mean())),
           "ref_std": float(want.std()), "rows": int(got.shape[0]),
           "argmax_agree": float((got.argmax(-1) == want.argmax(-1)).mean()),
           "atol": atol, "rms_rel_limit": rms_rel}
    out["ok"] = bool(np.isfinite(got).all()
                     and out["max_abs_diff"] <= atol
                     and out["rms_rel"] <= rms_rel)
    return out
