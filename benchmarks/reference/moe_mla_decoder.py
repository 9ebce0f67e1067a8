"""Plain reference of a pre-norm decoder with latent attention (MLA) and
routed experts: the ``glm4_moe_lite`` / DeepSeek-V2 block, as GLM-4.7-Flash
publishes it.

Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision("highest")``: no cache, no batching, no
absorption (every key and value is built from its latent), no sort and no
grouped matmul (every expert runs over every token and the tokens it was
not chosen for are weighted 0), the whole sequence at once.  It shares no
code with the program under test.  It walks the layers one at a time, and
inside an expert layer the experts one at a time, casting ONE expert of the
served (bf16) weights to float32 at a time, so 7 layers at full width fit
beside the model on the chip.

For a token ``x`` at position ``p`` (RMSNorm eps from the file)::

    c_q = RMSNorm(W_DQ x);  q_h = W_UQ c_q = q_nope_h | q_rope_h
    c_kv | k_r = W_DKV x;   c_kv = RMSNorm(c_kv)
    q_rope_h = RoPE(q_rope_h, p);  k_r = RoPE(k_r, p)      (one k_r for all heads)
    k_nope_h | v_h = W_UKV c_kv
    score_hs = (q_nope_h . k_nope_hs + q_rope_h . k_r,s) / sqrt(nope + rope)
    attention out = W_O concat_h sum_s softmax_s(score_h) v_hs

    layer < first_k_dense_replace:  SwiGLU of width intermediate_size
    else:  s = sigmoid(W_g x) (float32);  I = top-k of s + b;
           w_i = routed_scaling_factor * s_i / sum_{j in I} s_j
           y = sum_{i in I} w_i E_i(x) + E_shared(x)

Departures from the published model, each also in the configuration file
under ``assumed``: no multi-token-prediction layer; RoPE pairs dimension
``i`` with ``i + rope/2`` (rotate-half) where the published code
interleaves — with seeded weights a fixed permutation of 64 columns of
``W_UQ`` and ``W_DKV``; ``n_group = topk_group = 1``, so the group step of
``noaux_tc`` is the identity and is not written.

Weights arrive as plain arrays, ``[in, out]`` for every matrix::

    {"embed": [V, H], "norm": [H], "head": [H, V], "layers": [{
        "in_norm", "q_a" [H, q_rank], "q_a_norm", "q_b" [q_rank, heads * (nope + rope)],
        "kv_a" [H, kv_rank + rope], "kv_a_norm", "kv_b" [kv_rank, heads * (nope + v)],
        "o" [heads * v, H], "post_norm",
        and EITHER "gate", "up", "down" (dense layer)
        OR "router" [H, E], "router_bias" [E], "experts_gate_up" [E, H, 2 F]
           (gate columns, then up), "experts_down" [E, F, H],
           "shared_gate", "shared_up", "shared_down"}, ...]}

``m`` is the configuration file (published keys).

**Tolerance, and routing near-ties** (``compare``).  The program computes
in bf16 with float32 accumulation, the reference in float32, on the same
bf16 weights; the limits ``atol`` (largest difference of a logit) and
``rms_rel`` (rms of the differences over the rms of the reference) lie
between what bf16 gives and what the nearest precision below gives (the
configuration file has both readings).  One thing no precision tolerance
can hold: the router picks the ``k`` largest of 64 scores, and where a
token's ``k``-th and ``k+1``-th biased scores lie closer together than the
error bf16 has put into the hidden state by then, the program picks the
other expert.  That is a correct computation of a different, equally valid
rounding of the same model, and its logits differ by a whole expert's
contribution (0.5-2.0 where a row that routed alike differs by 0.05-0.07:
PERF.md section 6, PR 29).  So ``reference_logits`` records every
position's routing margin (the ``k``-th less the ``k+1``-th biased score,
the smallest over the expert layers), and ``compare`` LEAVES OUT a row
only where BOTH hold: its margin is under ``margin_eps`` AND it differs by
more than ``atol``.  A row with a clear margin must agree; a row with a
small margin that agrees is compared like any other.  The share left out
is printed, and the run fails when it is above ``max_left_out_share`` or
when no row is left.  Leaving out every row under the margin, whatever its
logits, was tried first and does not work at six expert layers of 64: on
the chip rows flipped at margins up to 0.005, and 50-83% of all rows have
a margin under 0.003.  In float32 on the CPU ``margin_eps`` is 1e-5 and
nothing is left out; the tests plant a bf16 router, a dropped shared
expert, a missing scaling factor, the bias used as a weight and a dropped
token, and each fails ``atol`` or ``rms_rel``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

# what the last reference_logits calls saw, for the compare that follows
# them: the harness hands compare() the logits alone
_SEEN: List = []
_CHECK: Dict = {}


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


def reference_logits(weights: Dict, m: Dict, ids: Sequence[int]):
    """float32 logits ``[len(ids), vocab]`` of one sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    heads = m["num_attention_heads"]
    nope, rope_d, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                        m["v_head_dim"])
    rank = m["kv_lora_rank"]
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    k = m["num_experts_per_tok"]
    scaling = float(m["routed_scaling_factor"])
    norm_topk = bool(m.get("norm_topk_prob", True))
    f32 = jnp.float32

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):
        inv = 1.0 / theta ** (jnp.arange(0, rope_d, 2, dtype=f32) / rope_d)
        ang = pos[:, None].astype(f32) * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : rope_d // 2], x[..., rope_d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ gate) * (h @ up)) @ down

    @jax.jit
    def attention(x, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(f32), w)
        t = x.shape[0]
        pos = jnp.arange(t)
        h = rms(x, w["in_norm"])
        q = (rms(h @ w["q_a"], w["q_a_norm"]) @ w["q_b"]).reshape(
            t, heads, nope + rope_d)
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos)
        kv = h @ w["kv_a"]
        c_kv = rms(kv[:, :rank], w["kv_a_norm"])
        k_r = rope(kv[:, None, rank:], pos)[:, 0]
        kv_up = (c_kv @ w["kv_b"]).reshape(t, heads, nope + vd)
        k_nope, v = kv_up[..., :nope], kv_up[..., nope:]
        s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
             + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) \
            / jnp.sqrt(f32(nope + rope_d))
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        return x + a.reshape(t, heads * vd) @ w["o"]

    @jax.jit
    def dense_ffn(x, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(f32), w)
        return x + swiglu(rms(x, w["post_norm"]), w["gate"], w["up"], w["down"])

    @jax.jit
    def route(x, w):
        h = rms(x, w["post_norm"].astype(f32))
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
    def shared(x, acc, h, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(f32), w)
        return x + acc + swiglu(h, w["shared_gate"], w["shared_up"],
                                w["shared_down"])

    @jax.jit
    def head(x, norm, w):
        return rms(x, norm.astype(f32)) @ w.astype(f32)

    margins = []
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(list(ids))].astype(f32)
        for w in weights["layers"]:
            small = {n: a for n, a in w.items() if not n.startswith("experts_")}
            x = attention(x, {n: small[n] for n in (
                "in_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
                "kv_b", "o")})
            if "router" not in w:
                x = dense_ffn(x, {n: small[n] for n in
                                  ("post_norm", "gate", "up", "down")})
                continue
            h, share, margin = route(x, {n: small[n] for n in (
                "post_norm", "router", "router_bias")})
            margins.append(margin)
            acc = jnp.zeros_like(x)
            for e in range(w["experts_gate_up"].shape[0]):
                acc = expert(acc, h, share[:, e], w["experts_gate_up"][e],
                             w["experts_down"][e])
            x = shared(x, acc, h, {n: small[n] for n in (
                "shared_gate", "shared_up", "shared_down")})
        out = head(x, weights["norm"], weights["head"])
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
    by more than ``atol`` is left out as a routing near-tie (module
    docstring); ``margins``, ``margin_eps`` and ``max_left_out_share``
    default to what the ``reference_logits`` calls before this one saw and
    to the ``check`` group of their configuration."""
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
