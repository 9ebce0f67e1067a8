"""Plain reference of the PARALLEL decoder block with sliding-window and
global attention layers and a share of the routed experts: the
``cohere2_moe`` block, as command-a-plus-05-2026 publishes it.

Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision("highest")``: the whole sequence at once, no
cache and no ring (a window layer is a banded mask), no sort and no grouped
matmul (every held expert runs over every token and the tokens it was not
chosen for are weighted 0), the four shared experts one at a time and
their mean taken.  It shares no code with the program under test.  It
walks the layers one at a time; inside a layer the key/value heads, the
held experts and the shared experts one at a time, casting ONE of them of
the served (bf16) weights to float32 at a time and attending in blocks of
queries, so four layers of 128 heads at 4,400 tokens fit beside the model
on the chip.

For layer ``i`` with ``u = LN_i(x)``, ``LN(x) = g * (x - mean(x)) /
sqrt(var(x) + layer_norm_eps)`` (no bias)::

    out = x + Attn_i(u) + FFN(u)

    q = W_q u [heads, d];  k, v = W_k u, W_v u [kv_heads, d]
    query head h reads key/value head h // (heads / kv_heads)
    sliding_attention: q, k rotated (RoPE, all d dimensions, rotate-half);
                       key s visible to query t iff 0 <= t - s < sliding_window
    full_attention:    nothing rotated; every s <= t visible
    Attn = W_o concat_h softmax_s(q_h . k_s / sqrt(d)) v_s

    s = sigmoid(W_r u) (float32, all 128);  I = the 8 largest
    w_i = s_i / sum_{j in I} s_j
    FFN = sum_{i in I, i held} w_i E_i(u) + 1/4 sum_{m=1..4} S_m(u)
    E(u) = W_down(silu(W_gate u) * W_up u)

    logits = logit_scale * LN_f(x) E^T      (the embedding slice, tied)

What the absent experts would add is left out, here as in the program.
Departures from the published model are the configuration file's
``assumed``.  Weights arrive as plain arrays, ``[in, out]`` for every
matrix::

    {"embed": [V, H], "norm": [H], "layers": [{
        "norm" [H], "q" [H, heads * d], "k", "v" [H, kv_heads * d],
        "o" [heads * d, H], "router" [H, E],
        "experts_gate_up" [E_held, H, 2 F] (gate columns, then up),
        "experts_down" [E_held, F, H],
        "shared_gate", "shared_up" [H, n_shared * F], "shared_down"
        [n_shared * F, H] (shared expert m owns columns / rows m F .. (m+1) F)
    }, ...]}

``m`` is the configuration file: ``num_experts`` is the number HELD,
``experts_held`` their ids into the router's ``n_routed_experts``.

**Tolerance, and routing near-ties.**  ``compare`` is
``moe_mla_decoder.py``'s, by its rule: a row is left out only where its
routing margin (the 8th less the 9th largest score, the smallest over the
layers) is under ``margin_eps`` AND it differs by more than ``atol``; 8 of
128 ties more often than 4 of 64.
"""

from __future__ import annotations

import os
from typing import Dict, Sequence

from benchmarks import harness

_tie = harness.load_module("reference", "moe_mla_decoder",
                           os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
compare = _tie.compare
QUERY_BLOCK = 128


def reference_logits(weights: Dict, m: Dict, ids: Sequence[int]):
    """float32 logits ``[len(ids), vocab]`` of one sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    heads, hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
    rep = heads // hkv
    eps, theta = float(m["layer_norm_eps"]), float(m["rope_theta"])
    window = int(m["sliding_window"])
    k_top = m["num_experts_per_tok"]
    n_shared = m["num_shared_experts"]
    held = list(m.get("experts_held") or range(m["num_experts"]))
    kinds = m["layer_types"][:m["num_hidden_layers"]]
    f32 = jnp.float32
    T = len(ids)
    pos = jnp.arange(T)

    def ln(x, g):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * g

    def rope(x):            # [T, n, d], rotate-half over all d dimensions
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=f32) / d)
        ang = pos[:, None].astype(f32) * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    @jax.jit
    def normed(x, g):
        return ln(x, g.astype(f32))

    def kv_group(windowed):
        @jax.jit
        def one(acc, u, wq, wk, wv, wo):
            # the rep query heads of ONE key/value head
            q = (u @ wq.astype(f32)).reshape(T, rep, d)
            k = (u @ wk.astype(f32)).reshape(T, 1, d)
            v = u @ wv.astype(f32)
            if windowed:
                q, k = rope(q), rope(k)
            k = k[:, 0]
            out = []
            for a in range(0, T, QUERY_BLOCK):
                qb, t = q[a:a + QUERY_BLOCK], pos[a:a + QUERY_BLOCK]
                s = jnp.einsum("qrd,kd->rqk", qb, k) / jnp.sqrt(f32(d))
                gap = t[:, None] - pos[None, :]
                seen = gap >= 0
                if windowed:
                    seen = seen & (gap < window)
                p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
                out.append(jnp.einsum("rqk,kd->qrd", p, v))
            o = jnp.concatenate(out, 0).reshape(T, rep * d)
            return acc + o @ wo.astype(f32)
        return one

    attend = {True: kv_group(True), False: kv_group(False)}

    @jax.jit
    def route(u, wr):
        s = jax.nn.sigmoid(u @ wr.astype(f32))
        order = jnp.argsort(-s, axis=-1)
        ranked = jnp.take_along_axis(s, order, axis=-1)
        chosen = jnp.zeros_like(s).at[
            jnp.arange(T)[:, None], order[:, :k_top]].set(1.0)
        w = s * chosen
        w = w / jnp.sum(w, -1, keepdims=True)
        return w, ranked[:, k_top - 1] - ranked[:, k_top]

    @jax.jit
    def swiglu(acc, u, share, gate, up, down):
        # one expert over EVERY token; ``share`` [T] weighs its result
        y = (jax.nn.silu(u @ gate.astype(f32)) * (u @ up.astype(f32))) \
            @ down.astype(f32)
        return acc + share[:, None] * y

    @jax.jit
    def head(x, g, emb):
        return float(m["logit_scale"]) * (ln(x, g.astype(f32))
                                          @ emb.astype(f32).T)

    margins = []
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(list(ids))].astype(f32)
        for w, kind in zip(weights["layers"], kinds):
            u = normed(x, w["norm"])
            att = jnp.zeros_like(x)
            for g in range(hkv):
                qs = slice(g * rep * d, (g + 1) * rep * d)
                ks = slice(g * d, (g + 1) * d)
                att = attend[kind == "sliding_attention"](
                    att, u, w["q"][:, qs], w["k"][:, ks], w["v"][:, ks],
                    w["o"][qs])
            share, margin = route(u, w["router"])
            margins.append(margin)
            ffn = jnp.zeros_like(x)
            f = w["experts_down"].shape[1]
            for e, expert_id in enumerate(held):
                gu = w["experts_gate_up"][e]
                ffn = swiglu(ffn, u, share[:, expert_id], gu[:, :f],
                             gu[:, f:], w["experts_down"][e])
            mean = jnp.full((T,), 1.0 / n_shared, f32)
            for s in range(n_shared):
                cols = slice(s * f, (s + 1) * f)
                ffn = swiglu(ffn, u, mean, w["shared_gate"][:, cols],
                             w["shared_up"][:, cols], w["shared_down"][cols])
            x = x + att + ffn
        out = head(x, weights["norm"], weights["embed"])
    _tie._SEEN.append(np.asarray(jnp.min(jnp.stack(margins), axis=0)))
    _tie._CHECK.clear()
    _tie._CHECK.update(m.get("check", {}))
    return out
