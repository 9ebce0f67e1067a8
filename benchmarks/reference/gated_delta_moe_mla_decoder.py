"""Plain reference of the ``gigachat3_5`` hybrid decoder
(GigaChat3.5-432B-A28B): gated delta-rule mixers among gated latent
attention, a dense SwiGLU or routed experts behind either, sandwich norms.

Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision("highest")``: a full forward pass of one
sequence, no cache, no chunking (the delta rule runs TOKEN BY TOKEN, one
``lax.scan`` step a token, the state a ``[heads, 128, 128]`` carry), no
absorption (every key and value is built from its latent), no sort and no
grouped matmul (every HELD expert runs over every token and the tokens it
was not chosen for are weighted 0).  It shares no code with the program
under test.  It walks the layers one at a time and casts the served (bf16)
weights to float32 a block of columns, an expert or a group of heads at a
time, so that five layers at the published widths fit beside the model and
its pools on the chip.

The equations (hidden ``H``; no biases; ``eps`` = ``rms_norm_eps``)::

    n(x; w)  = x / sqrt(mean(x^2) + eps) * g sigmoid(w)      g = layernorm_gating_weight
    block    : x <- x + n2(Mixer(n1(x)));  x <- x + n4(FFN(n3(x)))
    swiglu   : W_d (silu(min(W_g u, L)) * clip(W_u u, -L, L))    L = swiglu_limit

    layer i not in full_attention_layers, the delta-rule mixer (H_k key
    heads, H_v value heads, head size d, convolution K, no bias):
      q | k | v | z = W_in u;   b | a = W_ba u
      q | k | v <- silu(sum_j w_c[j] * (q | k | v)[t - K + 1 + j])
      per value head h, with q, k of key head h // (H_v / H_k):
      q <- q / ||q|| / sqrt(d);  k <- k / ||k||
      beta = sigmoid(b_h);  alpha = exp(-exp(A_log_h) softplus(a_h + dt_bias_h))
      S_t = alpha S_{t-1} + beta k (v - alpha S_{t-1}^T k)^T;   o = S_t^T q
      y = o / sqrt(mean(o^2) + eps_o) * (1 + w_o) * g_o sigmoid(z_h)
      Mixer = W_out concat_h y

    layer i in full_attention_layers, latent attention (moe_mla_decoder.py's
    equations, YaRN's frequencies and factors from hc_moe_mla_decoder
    .yarn_inv_freq) with an OUTPUT GATE: o <- o * sigmoid(W_g u), elementwise
    on the heads' values, before W_O

    FFN of layer i < first_k_dense_replace: the SwiGLU of width
    intermediate_size; else s = sigmoid(W_r u) (float32), I = the
    num_experts_per_tok largest of s + bias, w_i = routed_scaling_factor
    s_i / sum_{j in I} s_j, FFN = sum_{i in I, i held} w_i E_i(u) + E_shared(u)

Departures from the published model are the configuration file's
``assumed``.  ``experts_held`` (ids into the router's outputs) says which
experts' weights ``experts_gate_up`` / ``experts_down`` stack; what the
absent ones would add is left out, as in the program.

Weights arrive as plain arrays, ``[in, out]`` for every matrix::

    {"embed": [V, H], "norm": [H], "head": [H, V], "layers": [{
        "n1", "n2", "n3", "n4",
        EITHER "in_proj" [H, 2 H_k d + 2 H_v d], "ba_proj" [H, 2 H_v],
               "conv_w" [K, 2 H_k d + H_v d], "a_log" [H_v], "dt_bias" [H_v],
               "o_norm" [d], "out_proj" [H_v d, H]
        OR     "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o",
               "g" [H, heads * v]                     (moe_mla_decoder.py's names)
        and EITHER "gate", "up", "down"
        OR     "router" [H, E], "router_bias" [E], "experts_gate_up"
               [E_held, H, 2 F], "experts_down" [E_held, F, H],
               "shared_gate", "shared_up", "shared_down"}, ...]}

``compare`` is ``moe_mla_decoder.py``'s, with its routing-margin rule, and
two rules of this configuration's own.  A prompt's rows are not all one
row's worth: where ``check`` gives ``max_left_out_a_prompt``, a run in which
the margin rule leaves out more than that share of ONE prompt's rows is not
correct, whatever the share over all rows.  And THE STATE ITSELF is
compared: logits after five layers in bf16 carry about 3% of rounding, under
which the precision of a recurrent state cannot be seen, so where
``weights["slot_states"]`` gives the served slot pools (the builder's
``reference_weights`` does), every ``reference_logits`` call reads the slot
the sequence it was given has just left -- the one slot whose state changed
since the call before -- and holds each delta-rule layer's ``S_T`` of the
token-by-token scan beside it, head by head: ``|S - S_ref|_F / |S_ref|_F``.
``compare`` holds the MEDIAN head of every layer to that layer's entry of
``check.state_rel`` (the median, because a few heads that forget within a
token carry the rounding of their gate's input many times over; a limit a
layer, because a layer's input has passed the rounding of every layer
before it and, behind a router, a token routed otherwise at a near-tie).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence

from benchmarks import harness

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_tie = harness.load_module("reference", "moe_mla_decoder", _HERE)
_yarn = harness.load_module("reference", "hc_moe_mla_decoder", _HERE)
_STATES: List = []       # a reference_logits call: {layer: [a head's error]}
COLUMNS = 4096          # columns of a wide matrix cast to float32 at a time
HEAD_GROUP = 8          # latent heads whose [T, T] scores are held at a time


def reference_logits(weights: Dict, m: Dict, ids: Sequence[int]):
    """float32 logits ``[len(ids), vocab]`` of one sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    heads = m["num_attention_heads"]
    nope, rope_d, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                        m["v_head_dim"])
    rank = m["kv_lora_rank"]
    eps = float(m["rms_norm_eps"])
    g_norm = float(m["layernorm_gating_weight"])
    limit = float(m["swiglu_limit"])
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    K = m["linear_conv_kernel_dim"]
    g_out = float(m["linear_sigmoid_gate_scale"])
    eps_o = float(m["linear_attn_o_norm_eps"])
    k_top = m["num_experts_per_tok"]
    scaling = float(m["routed_scaling_factor"])
    norm_topk = bool(m.get("norm_topk_prob", True))
    held = list(m.get("experts_held") or range(m["n_routed_experts"]))
    attention = set(m["full_attention_layers"])
    inv_freq, rope_factor, scale_factor = _yarn.yarn_inv_freq(m)
    f32 = jnp.float32
    T = len(ids)
    pos = jnp.arange(T)

    def n(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * (g_norm * jax.nn.sigmoid(w.astype(f32)))

    def rope(x):
        ang = pos[:, None].astype(f32) * jnp.asarray(inv_freq, f32)[None, :]
        cos = jnp.cos(ang)[:, None, :] * rope_factor
        sin = jnp.sin(ang)[:, None, :] * rope_factor
        x1, x2 = x[..., : rope_d // 2], x[..., rope_d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    matmul = jax.jit(lambda u, w: u @ w.astype(f32))
    normed = jax.jit(n)

    def wide(u, w):
        """``u @ w`` with ``w`` cast :data:`COLUMNS` columns at a time."""
        return jnp.concatenate([matmul(u, w[:, c:c + COLUMNS])
                                for c in range(0, w.shape[1], COLUMNS)], -1)

    @jax.jit
    def swiglu_part(acc, u, share, gate, up, down):
        gate, up, down = gate.astype(f32), up.astype(f32), down.astype(f32)
        y = (jax.nn.silu(jnp.minimum(u @ gate, limit))
             * jnp.clip(u @ up, -limit, limit)) @ down
        return acc + share[:, None] * y

    def swiglu(u, gate, up, down, width=2048):
        """A SwiGLU of any width, ``width`` of its columns at a time."""
        acc, one = jnp.zeros_like(u), jnp.ones((T,), f32)
        for c in range(0, gate.shape[1], width):
            acc = swiglu_part(acc, u, one, gate[:, c:c + width],
                              up[:, c:c + width], down[c:c + width])
        return acc

    @jax.jit
    def delta_rule(qkv, z, ba, w):
        conv_w, a_log, dt_bias, o_norm = (
            w[k].astype(f32) for k in ("conv_w", "a_log", "dt_bias", "o_norm"))
        padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), f32), qkv])
        qkv = jax.nn.silu(sum(conv_w[j] * padded[j:j + T] for j in range(K)))
        q = qkv[:, :hk * dk].reshape(T, hk, dk)
        k = qkv[:, hk * dk:2 * hk * dk].reshape(T, hk, dk)
        v = qkv[:, 2 * hk * dk:].reshape(T, hv, dv)
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            / jnp.sqrt(f32(dk))
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
        beta = jax.nn.sigmoid(ba[:, :hv])
        alpha = jnp.exp(-jnp.exp(a_log)
                        * jax.nn.softplus(ba[:, hv:] + dt_bias))

        def token(S, inp):          # S [hv, dk, dv]
            q_t, k_t, v_t, b_t, a_t = inp
            S = a_t[:, None, None] * S
            held_v = jnp.einsum("hkv,hk->hv", S, k_t)
            S = S + jnp.einsum("hk,hv->hkv", k_t,
                               b_t[:, None] * (v_t - held_v))
            return S, jnp.einsum("hkv,hk->hv", S, q_t)

        S_T, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), f32),
                              (q, k, v, beta, alpha))
        y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps_o) \
            * (1.0 + o_norm) * (g_out * jax.nn.sigmoid(z.reshape(T, hv, dv)))
        return y.reshape(T, hv * dv), S_T

    def delta_mixer(u, w):
        """The mixer's output and the state after the last token."""
        conv_dim = w["conv_w"].shape[1]
        qkvz = wide(u, w["in_proj"])
        y, S_T = delta_rule(qkvz[:, :conv_dim], qkvz[:, conv_dim:],
                       matmul(u, w["ba_proj"]),
                       {k: w[k] for k in ("conv_w", "a_log", "dt_bias",
                                          "o_norm")})
        return wide(y, w["out_proj"]), S_T

    @jax.jit
    def latents(u, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(f32), w)

        def rms(x, g):
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + eps) * g

        c_q = rms(u @ w["q_a"], w["q_a_norm"])
        kv = u @ w["kv_a"]
        return (c_q, rms(kv[:, :rank], w["kv_a_norm"]),
                rope(kv[:, None, rank:])[:, 0])

    @jax.jit
    def attend(c_q, c_kv, k_r, q_b, kv_b):
        """A group of heads: ``[T, group * v]``."""
        n_h = q_b.shape[1] // (nope + rope_d)
        q = (c_q @ q_b.astype(f32)).reshape(T, n_h, nope + rope_d)
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:])
        kv_up = (c_kv @ kv_b.astype(f32)).reshape(T, n_h, nope + vd)
        k_nope, v = kv_up[..., :nope], kv_up[..., nope:]
        s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
             + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) \
            * (scale_factor / jnp.sqrt(f32(nope + rope_d)))
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                          v).reshape(T, n_h * vd)

    gate_of = jax.jit(lambda o, u, w: o * jax.nn.sigmoid(u @ w.astype(f32)))

    def latent_mixer(u, w):
        c_q, c_kv, k_r = latents(u, {k: w[k] for k in (
            "q_a", "q_a_norm", "kv_a", "kv_a_norm")})
        qw, kvw = nope + rope_d, nope + vd
        o = jnp.concatenate([
            attend(c_q, c_kv, k_r, w["q_b"][:, h * qw:(h + HEAD_GROUP) * qw],
                   w["kv_b"][:, h * kvw:(h + HEAD_GROUP) * kvw])
            for h in range(0, heads, HEAD_GROUP)], -1)
        cols = [gate_of(o[:, c:c + COLUMNS], u, w["g"][:, c:c + COLUMNS])
                for c in range(0, o.shape[1], COLUMNS)]
        return wide(jnp.concatenate(cols, -1), w["o"])

    @jax.jit
    def route(u, router, bias):
        scores = jax.nn.sigmoid(u @ router.astype(f32))
        return _tie.routing(scores, bias.astype(f32), k_top, scaling,
                            norm_topk)

    def experts(u, w):
        share, margin = route(u, w["router"], w["router_bias"])
        f = w["experts_down"].shape[1]
        acc = jnp.zeros_like(u)
        for e, expert_id in enumerate(held):
            gu = w["experts_gate_up"][e]
            acc = swiglu_part(acc, u, share[:, expert_id], gu[:, :f],
                              gu[:, f:], w["experts_down"][e])
        return acc + swiglu(u, w["shared_gate"], w["shared_up"],
                            w["shared_down"]), margin

    add = jax.jit(lambda x, y, w: x + n(y, w))
    margins, final_states = [], {}      # layer -> S_T [hv, dk, dv]
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(list(ids))].astype(f32)
        for i, w in enumerate(weights["layers"]):
            u = normed(x, w["n1"])
            if i in attention:
                mixed = latent_mixer(u, w)
            else:
                mixed, final_states[i] = delta_mixer(u, w)
            x = add(x, mixed, w["n2"])
            u = normed(x, w["n3"])
            if i < m["first_k_dense_replace"]:
                ffn = swiglu(u, w["gate"], w["up"], w["down"])
            else:
                ffn, margin = experts(u, w)
                margins.append(margin)
            x = add(x, ffn, w["n4"])
        out = wide(normed(x, weights["norm"]), weights["head"])
    if margins:
        _tie._SEEN.append(np.asarray(jnp.min(jnp.stack(margins), axis=0)))
    _tie._CHECK.clear()
    _tie._CHECK.update(m.get("check", {}))
    if weights.get("slot_states") is not None:
        _STATES.append(_state_errors(weights, final_states))
    return out


def _state_errors(weights: Dict, final_states: Dict) -> Optional[Dict]:
    """``{layer: [a head's |S - S_ref|_F / |S_ref|_F]}`` of the slot that the
    sequence just served has left: the one slot (the null slot 0 aside)
    whose state in the first delta-rule layer is not what it was at the
    call before (``weights["slot_norms"]``, first taken by the builder's
    ``reference_weights``).  ``None`` where that is not one slot: nothing
    was served since, or more than one sequence was."""
    import jax.numpy as jnp
    import numpy as np

    pools = weights["slot_states"]()
    if not pools:
        return None
    norms = np.asarray(jnp.sum(jnp.square(pools[0]), axis=(1, 2, 3)))
    changed = np.flatnonzero(norms[1:] != weights["slot_norms"][1:]) + 1
    weights["slot_norms"] = norms
    if len(changed) != 1:
        return None
    slot = int(changed[0])
    errors = {}
    for pool, (layer, want) in zip(pools, sorted(final_states.items())):
        got, want = np.asarray(pool[slot]), np.asarray(want)
        errors[layer] = np.sqrt(np.sum((got - want) ** 2, axis=(1, 2))
                                / np.sum(want ** 2, axis=(1, 2)))
    return errors


def compare(got, want, atol: float, rms_rel: float, margins=None,
            margin_eps: Optional[float] = None,
            max_left_out_share: Optional[float] = None) -> Dict:
    """``moe_mla_decoder.compare`` and this configuration's two rules (module
    docstring), by the ``check`` group of the ``reference_logits`` calls
    before this one and by the states they read."""
    import numpy as np

    if margins is None:
        margins = _tie.seen_margins(len(want))
    out = _tie.compare(got, want, atol, rms_rel, margins, margin_eps,
                       max_left_out_share)
    chk = _tie._CHECK
    per = int(chk.get("decode_steps", -1)) + 1
    share = chk.get("max_left_out_a_prompt")
    if share is not None and margins is not None \
            and out["rows"] == per * len(chk["prompt_lens"]):
        left = (np.asarray(margins) < out["margin_eps"]) \
            & (np.abs(np.asarray(got, np.float32)
                      - np.asarray(want, np.float32)).max(-1) > atol)
        out["left_out_a_prompt"] = [int(n) for n in
                                    left.reshape(-1, per).sum(-1)]
        out["max_left_out_a_prompt"] = float(share)
        out["ok"] &= max(out["left_out_a_prompt"]) <= share * per
    seen, _STATES[:] = list(_STATES), []
    limits = chk.get("state_rel")
    if seen and None not in seen:
        out["state_rel_err"] = {
            str(layer): round(max(float(np.median(s[layer])) for s in seen), 6)
            for layer in seen[0]}
        out["state_rel_err_worst_head"] = {
            str(layer): round(max(float(s[layer].max()) for s in seen), 6)
            for layer in seen[0]}
        if limits is not None:
            out["state_rel_limits"] = limits
            out["ok"] &= all(err <= float(limits[layer])
                             for layer, err in out["state_rel_err"].items())
    elif limits is not None:
        out["ok"] = False       # the check holds the state: it was not read
        out["state_rel_err"] = None
    return out
