"""Plain reference of a decoder whose residual path is ``n`` streams mixed
by manifold-constrained hyper-connections (DeepSeek's mHC, arXiv
2512.24880) around latent attention (MLA, YaRN-scaled RoPE) and routed
experts: the ``xing4_0`` block, as Xing4.0-29B-A4B publishes it.

Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision("highest")``: no cache, no batching, no
absorption (every key and value is built from its latent), no sort and no
grouped matmul (every expert runs over every token, weighted 0 where it was
not chosen), the whole sequence at once.  It imports nothing of the program
under test.  So that six layers at the published widths fit on the chip
beside the served model it walks the layers one at a time, the experts of
a layer one at a time (ONE expert of the served bf16 weights cast to
float32 at a time), the queries of the attention in blocks of
``QUERY_BLOCK`` (32 heads x 4,104 x 4,104 float32 scores are 2.2 GB whole)
and the output head in blocks of ``VOCAB_BLOCK`` columns, each block copied
to the host: the logits come back as a numpy array.

``n = hc_mult`` streams of ``C = hidden_size``; a token's state between
sublayers is ``X [n, C]``, after the embedding ``X_i = E[token]`` for every
``i``, after the last layer ``h = sum_i X_i`` and ``logits = W_head
RMSNorm_f(h)``.  Around a sublayer ``F`` with ``phi [n C, 2n + n n]``,
``offsets`` (``b_pre [n] | b_post [n] | B_res [n, n]`` row-major) and
``gains`` (``a_pre, a_post, a_res``), eps = ``rms_norm_eps``::

    x~ = vec(X) / sqrt(mean(vec(X)^2) + eps);     [p | q | r] = x~ phi
    H_pre = sigmoid(a_pre p + b_pre);   H_post = 2 sigmoid(a_post q + b_post)
    M = exp(clip(a_res mat(r) + B_res, clamp_min, clamp_max))
    hc_sinkhorn_iters times:  M <- M / (colsum(M) + hc_eps)   (colsum_j = sum_i M_ij)
                              M <- M / (rowsum(M) + hc_eps)
    u = sum_i H_pre[i] X_i;   y = F(RMSNorm(u))
    X'_i = sum_j M[i, j] X_j + H_post[i] y

Attention, for a token ``x`` at position ``p`` (32 heads, nope 128, rope
64, value 128 at the published widths)::

    c_q = RMSNorm(W_DQ x);  q_h = W_UQ c_q = q_nope_h | q_rope_h
    c_kv | k_r = W_DKV x;   c_kv = RMSNorm(c_kv)
    q_rope_h = RoPE(q_rope_h, p);  k_r = RoPE(k_r, p)      (one k_r for all heads)
    k_nope_h | v_h = W_UKV c_kv
    score_hs = (q_nope_h . k_nope_hs + q_rope_h . k_r,s) m(s, mscale_all_dim)^2 / sqrt(nope + rope)
    out = W_O concat_h sum_s softmax_s(score_h) v_hs

with YaRN's frequencies (``d`` = rope, ``s`` = factor, ``L0`` = original
positions): ``f_i = theta^(-2i/d)``; ``dim(b) = d ln(L0 / (2 pi b)) / (2 ln
theta)``; ``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``;
``g_i = clip((i - low) / (high - low), 0, 1)``; ``f'_i = (1 - g_i) f_i + g_i
f_i / s``; cos and sin times ``m(s, mscale) / m(s, mscale_all_dim)``,
``m(s, a) = 0.1 a ln s + 1``.

Feed-forward: layers before ``first_k_dense_replace`` SwiGLU of
``intermediate_size``; the others ``s = sigmoid(W_r u)`` in float32, the
``num_experts_per_tok`` largest of ``s + bias``, ``w_i =
routed_scaling_factor s_i / sum_chosen s_j``, ``sum_i w_i E_i(u) + S(u)``.

Departures from the published model, each also in the configuration file
under ``assumed``: the norm before ``phi`` has no learned scale; columns
before rows in an iteration; clip before ``exp``; streams start as copies
and end as a sum; rotate-half RoPE pairing; ``n_group = topk_group = 1``;
no multi-token-prediction layer.

Weights arrive as plain arrays, ``[in, out]`` for every matrix::

    {"embed": [V, C], "norm": [C], "head": [C, V], "layers": [{
        "attn_hc": {"phi", "offsets", "gains"}, "mlp_hc": {...},
        "in_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b",
        "o", "post_norm",
        and EITHER "gate", "up", "down" (dense layer)
        OR "router" [C, E], "router_bias" [E], "experts_gate_up" [E, C, 2 F]
           (gate columns, then up), "experts_down" [E, F, C],
           "shared_gate", "shared_up", "shared_down"}, ...]}

``compare`` is ``reference/moe_mla_decoder.py``'s rule, written again here
(the two references share nothing by import): a row is LEFT OUT only
where its smallest routing margin over the expert layers is under
``margin_eps`` AND it differs by more than ``atol`` — the program picked
another expert at a near-tie, a correct computation of another rounding of
the same model; the share left out is printed and bounded.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

QUERY_BLOCK = 512
VOCAB_BLOCK = 16384

# what the last reference_logits calls saw, for the compare that follows
# them: the harness hands compare() the logits alone
_SEEN: List = []
_CHECK: Dict = {}


def yarn_inv_freq(m: Dict):
    """``(frequencies [rope / 2], factor on cos and sin, factor on the
    softmax scale)`` of the configuration ``m``."""
    import numpy as np

    d, theta = m["qk_rope_head_dim"], float(m["rope_theta"])
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    rs = m.get("rope_scaling")
    if rs is None:
        return inv, 1.0, 1.0
    s, l0 = float(rs["factor"]), rs["original_max_position_embeddings"]

    def dim(b):
        return d * math.log(l0 / (2 * math.pi * b)) / (2 * math.log(theta))

    low = max(math.floor(dim(rs["beta_fast"])), 0)
    high = min(math.ceil(dim(rs["beta_slow"])), d - 1)
    g = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0.0, 1.0)

    def mscale(a):
        return 0.1 * a * math.log(s) + 1.0 if s > 1 else 1.0

    return ((1 - g) * inv + g * inv / s,
            mscale(rs.get("mscale", 1)) / mscale(rs.get("mscale_all_dim", 0)),
            mscale(rs.get("mscale_all_dim", 0)) ** 2)


def routing(scores, bias, k: int, scale: float, normalize: bool = True):
    """``(weights [T, E], margin [T])``: a token's weight for every expert
    (0 where not chosen) and its ``k``-th less ``k+1``-th biased score."""
    import jax.numpy as jnp

    biased = scores + bias
    order = jnp.argsort(-biased, axis=-1)
    ranked = jnp.take_along_axis(biased, order, axis=-1)
    chosen = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], order[:, :k]].set(1.0)
    w = scores * chosen
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale, ranked[:, k - 1] - ranked[:, k]


def hyper_coefficients(x, hc, n: int, iters: int, eps: float, hc_eps: float,
                       lo: float, hi: float):
    """``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` of the streams
    ``x [T, n, C]``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t = x.shape[0]
    v = x.reshape(t, -1)
    xt = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
    proj = xt @ hc["phi"].astype(f32)
    offs, gains = hc["offsets"].astype(f32), hc["gains"].astype(f32)
    h_pre = jax.nn.sigmoid(gains[0] * proj[:, :n] + offs[:n])
    h_post = 2.0 * jax.nn.sigmoid(gains[1] * proj[:, n:2 * n] + offs[n:2 * n])
    raw = gains[2] * proj[:, 2 * n:].reshape(t, n, n) \
        + offs[2 * n:].reshape(n, n)
    mat = jnp.exp(jnp.clip(raw, lo, hi))
    for _ in range(iters):
        mat = mat / (jnp.sum(mat, axis=1, keepdims=True) + hc_eps)
        mat = mat / (jnp.sum(mat, axis=2, keepdims=True) + hc_eps)
    return h_pre, h_post, mat


def reference_logits(weights: Dict, m: Dict, ids: Sequence[int]):
    """float32 logits ``[len(ids), vocab]`` of one sequence (numpy)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    heads = m["num_attention_heads"]
    nope, rope_d, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                        m["v_head_dim"])
    rank = m["kv_lora_rank"]
    eps = float(m["rms_norm_eps"])
    k = m["num_experts_per_tok"]
    scaling = float(m["routed_scaling_factor"])
    norm_topk = bool(m.get("norm_topk_prob", True))
    n, iters = int(m["hc_mult"]), int(m["hc_sinkhorn_iters"])
    hc_eps = float(m["hc_eps"])
    lo, hi = float(m["mhc_h_res_clamp_min"]), float(m["mhc_h_res_clamp_max"])
    inv_freq, rope_factor, scale_factor = yarn_inv_freq(m)
    f32 = jnp.float32

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):
        ang = pos[:, None].astype(f32) * jnp.asarray(inv_freq, f32)[None, :]
        cos = jnp.cos(ang)[:, None, :] * rope_factor
        sin = jnp.sin(ang)[:, None, :] * rope_factor
        x1, x2 = x[..., : rope_d // 2], x[..., rope_d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ gate) * (h @ up)) @ down

    def as_f32(w):
        return jax.tree_util.tree_map(lambda a: a.astype(f32), w)

    @jax.jit
    def read(x, hc):
        """Streams ``[T, n, C]`` -> ``(u [T, C], H_post, H_res)``."""
        h_pre, h_post, h_res = hyper_coefficients(x, hc, n, iters, eps,
                                                  hc_eps, lo, hi)
        return jnp.einsum("ti,tic->tc", h_pre, x), h_post, h_res

    @jax.jit
    def write(x, h_post, h_res, y):
        return jnp.einsum("tij,tjc->tic", h_res, x) \
            + h_post[:, :, None] * y[:, None, :]

    @jax.jit
    def keys_values(u, w):
        w = as_f32(w)
        t = u.shape[0]
        pos = jnp.arange(t)
        h = rms(u, w["in_norm"])
        q = (rms(h @ w["q_a"], w["q_a_norm"]) @ w["q_b"]).reshape(
            t, heads, nope + rope_d)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos)], -1)
        kv = h @ w["kv_a"]
        c_kv = rms(kv[:, :rank], w["kv_a_norm"])
        k_r = rope(kv[:, None, rank:], pos)[:, 0]
        kv_up = (c_kv @ w["kv_b"]).reshape(t, heads, nope + vd)
        return q, kv_up[..., :nope], k_r, kv_up[..., nope:]

    @jax.jit
    def attend(q, first, k_nope, k_r, v):
        """The queries ``q`` at positions ``first + [0, len(q))``."""
        s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope)
             + jnp.einsum("qhd,kd->hqk", q[..., nope:], k_r)) \
            * (scale_factor / math.sqrt(nope + rope_d))
        rows = first + jnp.arange(q.shape[0])
        s = jnp.where(rows[None, :, None] >= jnp.arange(v.shape[0])[None, None, :],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v).reshape(
            q.shape[0], heads * vd)

    @jax.jit
    def project(a, o):
        return a @ o.astype(f32)

    @jax.jit
    def dense_ffn(u, w):
        w = as_f32(w)
        return swiglu(rms(u, w["post_norm"]), w["gate"], w["up"], w["down"])

    @jax.jit
    def route(u, w):
        h = rms(u, w["post_norm"].astype(f32))
        scores = jax.nn.sigmoid(h @ w["router"].astype(f32))
        return (h,) + routing(scores, w["router_bias"].astype(f32), k,
                              scaling, norm_topk)

    @jax.jit
    def expert(acc, h, share, gate_up, down):
        # one expert over EVERY token; ``share`` [T] is 0 where not chosen
        gate_up, down = gate_up.astype(f32), down.astype(f32)
        f = down.shape[0]
        y = (jax.nn.silu(h @ gate_up[:, :f]) * (h @ gate_up[:, f:])) @ down
        return acc + share[:, None] * y

    @jax.jit
    def shared(acc, h, w):
        w = as_f32(w)
        return acc + swiglu(h, w["shared_gate"], w["shared_up"],
                            w["shared_down"])

    @jax.jit
    def final(x, norm):
        return rms(jnp.sum(x, axis=1), norm.astype(f32))

    @jax.jit
    def head(h, w):
        return h @ w.astype(f32)

    margins = []
    with jax.default_matmul_precision("highest"):
        e = weights["embed"][jnp.asarray(list(ids))].astype(f32)
        x = jnp.repeat(e[:, None, :], n, axis=1)            # [T, n, C]
        for w in weights["layers"]:
            u, h_post, h_res = read(x, w["attn_hc"])
            q, k_nope, k_r, v = keys_values(u, {name: w[name] for name in (
                "in_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
                "kv_b")})
            a = jnp.concatenate([
                attend(q[i:i + QUERY_BLOCK], i, k_nope, k_r, v)
                for i in range(0, q.shape[0], QUERY_BLOCK)])
            x = write(x, h_post, h_res, project(a, w["o"]))
            u, h_post, h_res = read(x, w["mlp_hc"])
            if "router" not in w:
                y = dense_ffn(u, {name: w[name] for name in
                                  ("post_norm", "gate", "up", "down")})
            else:
                h, share, margin = route(u, {name: w[name] for name in (
                    "post_norm", "router", "router_bias")})
                margins.append(margin)
                y = jnp.zeros_like(u)
                for i in range(w["experts_gate_up"].shape[0]):
                    y = expert(y, h, share[:, i], w["experts_gate_up"][i],
                               w["experts_down"][i])
                y = shared(y, h, {name: w[name] for name in (
                    "shared_gate", "shared_up", "shared_down")})
            x = write(x, h_post, h_res, y)
        hid = final(x, weights["norm"])
        vocab = weights["head"].shape[1]
        out = np.concatenate([
            np.asarray(head(hid, weights["head"][:, i:i + VOCAB_BLOCK]))
            for i in range(0, vocab, VOCAB_BLOCK)], axis=1)
    if margins:
        _SEEN.append(np.asarray(jnp.min(jnp.stack(margins), axis=0)))
    _CHECK.clear()
    _CHECK.update(m.get("check", {}))
    return out


def seen_margins(rows: int):
    """The routing margins of the ``rows`` rows the harness compares: it
    runs ``reference_logits`` once a prompt and compares the LAST rows of
    each (the prompt's last position and the decode steps), the same
    number from every call.  Clears the record."""
    import numpy as np

    calls, _SEEN[:] = list(_SEEN), []
    if not calls or rows % len(calls):
        return None
    per = rows // len(calls)
    if any(len(c) < per for c in calls):
        return None
    return np.concatenate([c[-per:] for c in calls])


def compare(got, want, atol: float, rms_rel: float, margins=None,
            margin_eps: Optional[float] = None,
            max_left_out_share: Optional[float] = None) -> Dict:
    """Program logits against reference logits, both ``[rows, vocab]``.
    A row whose routing margin is under ``margin_eps`` AND which differs
    by more than ``atol`` is left out as a routing near-tie; ``margins``,
    ``margin_eps`` and ``max_left_out_share`` default to what the
    ``reference_logits`` calls before this one saw and to the ``check``
    group of their configuration."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if margins is None:
        margins = seen_margins(want.shape[0])
    if margin_eps is None:
        margin_eps = float(_CHECK.get("margin_eps", 0.0))
    if max_left_out_share is None:
        max_left_out_share = float(_CHECK.get("max_left_out_share", 0.0))
    row_max = np.abs(got - want).max(axis=-1)
    keep = np.ones(want.shape[0], bool) if margins is None \
        else ~((np.asarray(margins) < margin_eps) & (row_max > atol))
    out = {"rows": int(got.shape[0]), "rows_compared": int(keep.sum()),
           "left_out_share": float(1.0 - keep.mean()),
           "max_left_out_share": max_left_out_share,
           "margin_eps": margin_eps, "atol": atol, "rms_rel_limit": rms_rel,
           "row_max_abs_diff": [round(float(v), 4) for v in row_max],
           "row_margin": None if margins is None
           else [round(float(v), 5) for v in margins]}
    if keep.any():
        diff = (got - want)[keep]
        out.update(
            max_abs_diff=float(np.abs(diff).max()),
            rms_rel=float(np.sqrt((diff ** 2).mean()
                                  / (want[keep] ** 2).mean())),
            ref_std=float(want[keep].std()),
            argmax_agree=float((got[keep].argmax(-1)
                                == want[keep].argmax(-1)).mean()))
    out["ok"] = bool(keep.any() and np.isfinite(got).all()
                     and out["left_out_share"] <= max_left_out_share
                     and out["max_abs_diff"] <= atol
                     and out["rms_rel"] <= rms_rel)
    return out
