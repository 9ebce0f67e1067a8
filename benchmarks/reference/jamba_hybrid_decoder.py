"""Plain reference of a pre-norm hybrid decoder: selective-scan (Mamba-1)
mixers with an attention layer every ``attn_layer_period`` layers, each
followed by a dense SwiGLU — the ``jamba`` block, as AI21-Jamba2-3B
publishes it (``num_experts`` 1: no routed layer).

Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision("highest")``: the recurrence is a
``lax.scan`` over positions ONE token at a time; no cache, no slots, no
chunking, no batching, the whole sequence from a zero state.  It shares no
code with the program under test.  It walks the layers one at a time and
casts ONE layer of the served (bf16) weights to float32 at a time, so 28
layers at full width fit beside the model on the chip.  Which layer is of
which kind is read HERE from ``attn_layer_period`` / ``attn_layer_offset``
(``i % period == offset`` is attention, as the published ``jamba`` code
reads them), not from the weights handed in.

Every layer ``i`` (RMSNorm eps from the file)::

    h = x + Mixer_i(RMSNorm(x));   out = h + W_down(silu(W_gate u) * W_up u),  u = RMSNorm(h)

    attention:  q = W_q u [heads, d], k, v = W_k u, W_v u [kv_heads, d]; NO rotation;
                score = q . k / sqrt(d), causal softmax; out = W_o concat_h(sum_s a_s v_s)

    mixer:      x | z       = W_in u                                  [D + D]
                x_t         = silu(sum_j w_c[j] x_{t-K+1+j} + b_c)    zeros before the sequence
                dl | B | C  = W_x x_t                                 [R + N + N]
                dl, B, C    = RMSNorm(dl), RMSNorm(B), RMSNorm(C)     (learned scales)
                dt          = softplus(W_dt dl + b_dt)
                H_t         = exp(dt (x) A) * H_{t-1} + (dt * x_t) (x) B_t,   A = -exp(A_log),  H_0 = 0
                y_t         = H_t C_t + D * x_t;   out = W_out (y_t * silu(z_t))

Final RMSNorm, logits ``h . E^T`` with the embedding ``E`` (tied head, no
embedding scale).  Departures from the published model are in the
configuration file under ``assumed``.

Weights arrive as plain arrays, ``[in, out]`` for every matrix::

    {"embed": [V, H], "norm": [H], "layers": [{
        "in_norm", "post_norm", "gate", "up", "down", and EITHER
        "q" [H, heads * d], "k", "v" [H, kv_heads * d], "o" [heads * d, H]
        OR "in_proj" [H, 2 D], "conv_w" [K, D], "conv_b" [D],
           "x_proj" [D, R + 2 N], "dt_norm" [R], "b_norm" [N], "c_norm" [N],
           "dt_proj" [R, D], "dt_bias" [D], "a_log" [N, D], "d" [D],
           "out_proj" [D, H]}, ...]}

``m`` is the configuration file (published keys).

Tolerance (``compare``): the program computes its matrix products in bf16
with float32 accumulation and the recurrence in float32, the reference
everything in float32, on the SAME bf16 weights.  The limits ``atol``
(largest difference of a logit) and ``rms_rel`` (rms of the differences
over the rms of the reference) come from the configuration file and lie
between what bf16 gives and what the nearest precision below gives
(``check.why`` has both readings).
"""

from __future__ import annotations

from typing import Dict, Sequence

MIXER_KEYS = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_norm", "b_norm",
              "c_norm", "dt_proj", "dt_bias", "a_log", "d", "out_proj")
ATTN_KEYS = ("q", "k", "v", "o")
FFN_KEYS = ("in_norm", "post_norm", "gate", "up", "down")


def is_attention_layer(m: Dict, i: int) -> bool:
    return i % int(m["attn_layer_period"]) == int(m["attn_layer_offset"])


def reference_logits(weights: Dict, m: Dict, ids: Sequence[int]):
    """float32 logits ``[len(ids), vocab]`` of one sequence."""
    import jax
    import jax.numpy as jnp

    heads, kv_heads = m["num_attention_heads"], m["num_key_value_heads"]
    d = int(m.get("head_dim") or m["hidden_size"] // heads)
    eps = float(m["rms_norm_eps"])
    D = int(m["mamba_expand"]) * int(m["hidden_size"])
    N, K, R = (int(m["mamba_d_state"]), int(m["mamba_d_conv"]),
               int(m["mamba_dt_rank"]))
    f32 = jnp.float32

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def ffn(x, w):
        u = rms(x, w["post_norm"])
        return x + (jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"]

    @jax.jit
    def attention_layer(x, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(f32), w)
        t = x.shape[0]
        pos = jnp.arange(t)
        u = rms(x, w["in_norm"])
        q = (u @ w["q"]).reshape(t, heads, d)
        k = jnp.repeat((u @ w["k"]).reshape(t, kv_heads, d),
                       heads // kv_heads, axis=1)
        v = jnp.repeat((u @ w["v"]).reshape(t, kv_heads, d),
                       heads // kv_heads, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(f32(d))
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        return ffn(x + a.reshape(t, heads * d) @ w["o"], w)

    @jax.jit
    def mixer_layer(x, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(f32), w)
        t = x.shape[0]
        xz = rms(x, w["in_norm"]) @ w["in_proj"]
        xs, z = xz[:, :D], xz[:, D:]
        padded = jnp.concatenate([jnp.zeros((K - 1, D), f32), xs], 0)
        conv = sum(w["conv_w"][j] * padded[j:j + t] for j in range(K))
        xs = jax.nn.silu(conv + w["conv_b"])
        dbc = xs @ w["x_proj"]
        dl = rms(dbc[:, :R], w["dt_norm"])
        B = rms(dbc[:, R:R + N], w["b_norm"])
        C = rms(dbc[:, R + N:], w["c_norm"])
        dt = jax.nn.softplus(dl @ w["dt_proj"] + w["dt_bias"])
        A = -jnp.exp(w["a_log"])                                  # [N, D]

        def token(H, inp):
            x_t, dt_t, b_t, c_t = inp
            H = jnp.exp(dt_t[None, :] * A) * H \
                + (dt_t * x_t)[None, :] * b_t[:, None]
            return H, c_t @ H
        _, y = jax.lax.scan(token, jnp.zeros((N, D), f32), (xs, dt, B, C))
        y = (y + w["d"] * xs) * jax.nn.silu(z)
        return ffn(x + y @ w["out_proj"], w)

    @jax.jit
    def head(x, norm, emb):
        return rms(x, norm.astype(f32)) @ emb.astype(f32).T

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(list(ids))].astype(f32)
        for i, w in enumerate(weights["layers"]):
            if is_attention_layer(m, i):
                x = attention_layer(x, {n: w[n] for n in FFN_KEYS + ATTN_KEYS})
            else:
                x = mixer_layer(x, {n: w[n] for n in FFN_KEYS + MIXER_KEYS})
        return head(x, weights["norm"], weights["embed"])


def compare(got, want, atol: float, rms_rel: float) -> Dict:
    """Program logits against reference logits, both ``[rows, vocab]``."""
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
