"""The gated delta rule and the benchmark's reference of the layer kind
built on it (ISSUE 49): the rule's chunked and carried forms against its
token-by-token recurrence, the plain reference against a naive per-head
loop in float64, and the accepted configurations' programs unchanged.
float32 on the CPU at toy widths (``gdn_common.py``); the model's paths
against the reference are in ``..._gated_delta_paths.py`` and the planted
faults in ``..._gated_delta_faults.py``: a file a test worker, so that
the files that sort last do not run one after another."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gdn_common import (ATOL, CHUNK, RMS_REL, TINY, builder, chunks_of_eight,
                        model, prompt_of, ref)     # noqa: F401  (fixtures)

from paddle_tpu.ops import gated_delta as gd


# --- the rule's three forms ---------------------------------------------------

def _inputs(T, B=2, H=4, d=16, seed=0):
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q = gd.l2_normalize(n(B, T, H, d)) / np.sqrt(d)
    k = gd.l2_normalize(n(B, T, H, d))
    beta, log_alpha = gd.gates(
        n(B, T, H), n(B, T, H),
        jnp.log(jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)),
        n(H) * 0.5)
    return (q, k, n(B, T, H, d), log_alpha, beta), n(B, H, d, d)


@pytest.mark.parametrize("T,n_valid,carried", [
    (16, None, False),      # whole chunks
    (37, None, False),      # ends inside a chunk
    (64, 29, False),        # a padded bucket: 29 real tokens of 64
    (37, None, True),       # starts from a slot's state
    (64, 41, True),
    (8, 3, True),           # one chunk, mostly padding
])
def test_the_chunked_form_equals_the_recurrence(T, n_valid, carried):
    x, s0 = _inputs(T)
    if not carried:
        s0 = jnp.zeros_like(s0)
    want_o, want_s = gd.gated_delta_recurrence(*x, s0, n_valid)
    got_o, got_s = gd.gated_delta_chunked(*x, s0, n_valid, chunk=CHUNK)
    real = T if n_valid is None else n_valid
    np.testing.assert_allclose(got_o[:, :real], want_o[:, :real], atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    assert float(jnp.abs(want_o).max()) > 0.05          # not a comparison of zeros


def test_padding_is_inert_and_two_chunks_carry_like_one_pass():
    """The state after a padded bucket is the state after its last REAL
    token, and a prompt taken in two launches (the second carried) leaves
    the state and the outputs of one launch."""
    x, s0 = _inputs(48)
    _, whole = gd.gated_delta_chunked(*x, s0, None, chunk=CHUNK)
    o29, s29 = gd.gated_delta_chunked(*(a[:, :29] for a in x), s0, None,
                                      chunk=CHUNK)
    _, padded = gd.gated_delta_chunked(*x, s0, 29, chunk=CHUNK)
    np.testing.assert_allclose(padded, s29, atol=2e-6)
    o_rest, carried = gd.gated_delta_chunked(*(a[:, 29:] for a in x), s29,
                                             None, chunk=CHUNK)
    np.testing.assert_allclose(carried, whole, atol=2e-6)
    o_whole, _ = gd.gated_delta_chunked(*x, s0, None, chunk=CHUNK)
    np.testing.assert_allclose(jnp.concatenate([o29, o_rest], 1), o_whole,
                               atol=2e-6)
    assert float(jnp.abs(padded - whole).max()) > 1e-3


def test_a_step_is_the_equation_read_before_written():
    """One head, one token, by hand: decay, read what the decayed state
    holds for k, write the correction."""
    (q, k, v, la, beta), s0 = _inputs(1, B=1, H=1, d=4, seed=3)
    o, s = gd.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], la[:, 0],
                               beta[:, 0], s0)
    S0, kk, vv, qq = (np.asarray(a, np.float64) for a in
                      (s0[0, 0], k[0, 0, 0], v[0, 0, 0], q[0, 0, 0]))
    a, b = float(np.exp(la[0, 0, 0])), float(beta[0, 0, 0])
    want = a * S0 + b * np.outer(kk, vv - a * S0.T @ kk)
    np.testing.assert_allclose(s[0, 0], want, atol=1e-6)
    np.testing.assert_allclose(o[0, 0], want.T @ qq, atol=1e-6)


# --- the reference against a naive loop ---------------------------------------

def naive_logits(w, m, ids):
    """The equations of ``gated_delta_moe_mla_decoder.py``'s docstring in
    numpy float64, a Python loop over tokens and heads: no scan, no
    batched product over heads, no routing table."""
    f = lambda a: np.asarray(a, np.float64)     # noqa: E731
    sig = lambda a: 1.0 / (1.0 + np.exp(-a))    # noqa: E731
    silu = lambda a: a * sig(a)                 # noqa: E731
    T, eps, L = len(ids), m["rms_norm_eps"], float(m["swiglu_limit"])
    hk, hv, d = (m["linear_num_key_heads"], m["linear_num_value_heads"],
                 m["linear_key_head_dim"])
    heads, nope, rope_d, vd, rank = (
        m["num_attention_heads"], m["qk_nope_head_dim"],
        m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"])
    inv, rope_factor, scale_factor = ref_module().yarn_inv_freq(m)

    def n(x, wn):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) \
            * (m["layernorm_gating_weight"] * sig(f(wn)))

    def swiglu(u, g, up, down):
        return (silu(np.minimum(u @ f(g), L))
                * np.clip(u @ f(up), -L, L)) @ f(down)

    def rot(x, t):
        ang = t * inv
        x1, x2 = x[..., :rope_d // 2], x[..., rope_d // 2:]
        c, s = np.cos(ang) * rope_factor, np.sin(ang) * rope_factor
        return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def delta(u, lw):
        conv_dim = f(lw["conv_w"]).shape[1]
        qkvz, ba = u @ f(lw["in_proj"]), u @ f(lw["ba_proj"])
        pad = np.concatenate([np.zeros((3, conv_dim)), qkvz[:, :conv_dim]])
        x = silu(sum(f(lw["conv_w"])[j] * pad[j:j + T] for j in range(4)))
        z = qkvz[:, conv_dim:].reshape(T, hv, d)
        out = np.zeros((T, hv, d))
        for h in range(hv):
            j = h // (hv // hk)
            S = np.zeros((d, d))
            A = np.exp(f(lw["a_log"])[h])
            for t in range(T):
                q = x[t, j * d:(j + 1) * d]
                k = x[t, (hk + j) * d:(hk + j + 1) * d]
                v = x[t, 2 * hk * d + h * d:2 * hk * d + (h + 1) * d]
                q = q / np.sqrt((q * q).sum() + 1e-6) / np.sqrt(d)
                k = k / np.sqrt((k * k).sum() + 1e-6)
                beta = sig(ba[t, h])
                alpha = np.exp(-A * np.log1p(np.exp(
                    ba[t, hv + h] + f(lw["dt_bias"])[h])))
                S = alpha * S
                S = S + beta * np.outer(k, v - S.T @ k)
                o = S.T @ q
                out[t, h] = o / np.sqrt((o * o).mean() + 1e-6) \
                    * (1 + f(lw["o_norm"])) * 2 * sig(z[t, h])
        return out.reshape(T, hv * d) @ f(lw["out_proj"])

    def latent(u, lw):
        def rms(x, g):
            return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * f(g)

        q = (rms(u @ f(lw["q_a"]), lw["q_a_norm"]) @ f(lw["q_b"])).reshape(
            T, heads, nope + rope_d)
        kv = u @ f(lw["kv_a"])
        c_kv = rms(kv[:, :rank], lw["kv_a_norm"])
        kv_up = (c_kv @ f(lw["kv_b"])).reshape(T, heads, nope + vd)
        gate = sig(u @ f(lw["g"])).reshape(T, heads, vd)
        out = np.zeros((T, heads, vd))
        for t in range(T):
            k_r = np.stack([rot(kv[s, rank:], s) for s in range(t + 1)])
            for h in range(heads):
                s = (kv_up[:t + 1, h, :nope] @ q[t, h, :nope]
                     + k_r @ rot(q[t, h, nope:], t)) \
                    * scale_factor / np.sqrt(nope + rope_d)
                p = np.exp(s - s.max())
                out[t, h] = (p / p.sum()) @ kv_up[:t + 1, h, nope:]
        return (out * gate).reshape(T, heads * vd) @ f(lw["o"])

    def experts(u, lw):
        s = sig(u @ f(lw["router"]))
        fw = f(lw["experts_down"]).shape[1]
        out = swiglu(u, lw["shared_gate"], lw["shared_up"], lw["shared_down"])
        for t in range(T):
            top = np.argsort(-(s[t] + f(lw["router_bias"])))[
                :m["num_experts_per_tok"]]
            for e in top:
                if e in m["experts_held"]:
                    i = m["experts_held"].index(e)
                    gu = f(lw["experts_gate_up"][i])
                    out[t] += m["routed_scaling_factor"] * s[t, e] \
                        / s[t, top].sum() * swiglu(
                            u[t], gu[:, :fw], gu[:, fw:],
                            lw["experts_down"][i])
        return out

    x = f(w["embed"])[np.asarray(ids)]
    for i, lw in enumerate(w["layers"]):
        u = n(x, lw["n1"])
        mixed = latent(u, lw) if i in m["full_attention_layers"] \
            else delta(u, lw)
        x = x + n(mixed, lw["n2"])
        u = n(x, lw["n3"])
        ffn = swiglu(u, lw["gate"], lw["up"], lw["down"]) \
            if i < m["first_k_dense_replace"] else experts(u, lw)
        x = x + n(ffn, lw["n4"])
    return n(x, w["norm"]) @ f(w["head"])


def ref_module():
    from benchmarks import harness

    return harness.load_module("reference", "hc_moe_mla_decoder")


def test_the_reference_agrees_with_a_naive_per_head_loop(model, builder, ref):
    ids = prompt_of(21, 4)
    w = builder.reference_weights(model)
    want = naive_logits(w, TINY, ids)
    got = np.asarray(ref.reference_logits(w, TINY, ids))
    assert want.shape == (21, TINY["vocab_size"])
    res = ref.compare(got, want, ATOL, RMS_REL, margin_eps=0.0,
                      max_left_out_share=0.0)
    assert res["ok"] and res["rows_compared"] == 21, res
    src = open(ref.__file__).read()
    assert "paddle_tpu" not in src.replace("``paddle_tpu", "")
    assert 'default_matmul_precision("highest")' in src
    assert "lax.scan(token" in src          # the rule, token by token


# --- the accepted configurations' programs are the parent's -----------------------------

ACCEPTED = ("llama_dense", "moe_mla", "mamba_hybrid", "window_moe",
            "hc_moe_mla", "eva", "llama_moe")


def _tiny(name):
    from paddle_tpu import models as M

    return {"llama_dense": lambda: M.LlamaConfig.tiny(num_hidden_layers=2),
            "llama_moe": lambda: M.LlamaConfig.tiny_moe(num_hidden_layers=2),
            "moe_mla": M.MoEMLAConfig.tiny,
            "mamba_hybrid": M.HybridMambaConfig.tiny,
            "window_moe": M.WindowMoEConfig.tiny,
            "hc_moe_mla": M.HCMoEMLAConfig.tiny,
            "eva": M.EvaConfig.tiny}[name]()


@pytest.mark.parametrize("name", ACCEPTED)
def test_an_accepted_configuration_traces_to_the_parents_program(
        name, monkeypatch):
    """Two traces made in THIS process (the text of a jaxpr is no constant
    of the program, ``test_zzz..._hc_moe_mla.py``): the model as it is,
    against the same model with everything this PR hung on the shared
    layers made unreachable -- the gate's projection, the clamp, the
    limit's argument.  The texts are equal and neither hook was entered."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import LlamaForCausalLM, llama, moe_mla
    from paddle_tpu.parallel import moe

    paddle.seed(5)
    m = LlamaForCausalLM(_tiny(name))
    m.eval()
    params = list(m.parameters())
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 96, (1, 24)))

    def f(vals, ids):
        saved = [p._value for p in params]
        for p, v in zip(params, vals):
            p._value = v
        try:
            with paddle.no_grad():
                return m(Tensor(ids))._value
        finally:
            for p, v in zip(params, saved):
                p._value = v

    def trace():
        return str(jax.make_jaxpr(f)([p._value for p in params], ids))

    text = trace()
    assert m.config.swiglu_limit is None
    assert not getattr(m.config, "gated_attention", False)

    def never(*a, **k):
        raise AssertionError("a hook of the gated delta-rule kind was "
                             "entered by an accepted configuration")

    real = moe._grouped_swiglu
    with monkeypatch.context() as mp:
        mp.setattr(moe, "clamped_swiglu", never)
        mp.setattr(llama, "clamped_swiglu", never)
        # the parent's signature: no limit is handed down
        mp.setattr(moe, "_grouped_swiglu",
                   lambda rows, wgu, wd, sizes, limit=None:
                   never() if limit is not None else real(rows, wgu, wd,
                                                           sizes))
        for layer in m.llama.layers:
            attn = getattr(layer, "self_attn", None)
            if isinstance(attn, moe_mla.LatentAttention):
                assert attn.g_proj is None
        assert trace() == text

