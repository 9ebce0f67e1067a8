"""Plain reference of a dense pre-norm decoder: RMSNorm -> rotary
embedding (half-rotation, as the published Llama / Mistral / DeepSeek-LLM
code has it) -> grouped-query or multi-head causal attention -> SwiGLU.

Straightforward ``jax.numpy`` in float32 at
``jax.default_matmul_precision("highest")``: no cache, no kernels, no
batching, the whole sequence at once.  It shares no code with the program
under test.  It walks the layers one at a time and casts ONE layer of the
served (bf16) weights to float32 at a time, so at full width and depth it
fits beside the model on the chip.

Weights arrive as plain arrays, ``[in, out]`` for every matrix::

    {"embed": [V, H], "norm": [H], "head": [H, V],
     "layers": [{"in_norm", "q", "k", "v", "o", "post_norm",
                 "gate", "up", "down"}, ...]}

``m`` is the ``model`` group of the configuration file (published keys).

Tolerance (``compare``): the program computes in bf16 with float32
accumulation, the reference in float32, on the SAME bf16 weights.  Each
layer's bf16 rounding (2^-8 relative) adds up through the residual stream;
at depth 16 and logits of standard deviation ~1.3 the largest of some
600,000 compared logits differs by ~0.1-0.25 (PERF.md section 6 has what
was measured).  The limits are ``max |diff| <= atol`` and ``rms(diff) /
rms(ref) <= rms_rel`` from the configuration file: about twice what bf16
gives, and well under what an 8-bit cache or weights (relative error
2^-4), a wrong page, a wrong mask or a dropped layer would give, which
move logits by whole units.
"""

from __future__ import annotations

from typing import Dict, Sequence


def reference_logits(weights: Dict, m: Dict, ids: Sequence[int]):
    """float32 logits ``[len(ids), vocab]`` of one sequence."""
    import jax
    import jax.numpy as jnp

    heads, kv_heads = m["num_attention_heads"], m["num_key_value_heads"]
    d = int(m.get("head_dim") or m["hidden_size"] // heads)
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    f32 = jnp.float32

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=f32) / d)
        ang = pos[:, None].astype(f32) * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    @jax.jit
    def layer(x, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(f32), w)
        t = x.shape[0]
        pos = jnp.arange(t)
        h = rms(x, w["in_norm"])
        q = rope((h @ w["q"]).reshape(t, heads, d), pos)
        k = rope((h @ w["k"]).reshape(t, kv_heads, d), pos)
        v = (h @ w["v"]).reshape(t, kv_heads, d)
        group = heads // kv_heads
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(f32(d))
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(t, heads * d) @ w["o"]
        h = rms(x, w["post_norm"])
        return x + (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]

    @jax.jit
    def head(x, norm, w):
        return rms(x, norm.astype(f32)) @ w.astype(f32)

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(list(ids))].astype(f32)
        for w in weights["layers"]:
            x = layer(x, w)
        return head(x, weights["norm"], weights["head"])


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
