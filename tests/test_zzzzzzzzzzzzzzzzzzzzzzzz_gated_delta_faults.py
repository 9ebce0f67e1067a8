"""Faults planted in the gated delta-rule layer kind that the comparison
with the benchmark's plain reference MUST catch (ISSUE 49): the decay
applied after the correction, beta left out, q not scaled, the state read
after the write, padding tokens updating the state, the gate on the latent
attention left out, the clamp left out at weights that reach it.  float32 on
the CPU at toy widths (``gdn_common.py``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gdn_common import (ATOL, CHUNK, TINY, build, builder, capture, check,
                        chunks_of_eight, forward, make_engine, model,
                        prompt_of, ref, serve,
                        served_logits)     # noqa: F401  (fixtures)


# --- planted faults: each MUST fail ---------------------------------------------------

def _recurrence_with(step):
    """``gated_delta_chunked``'s signature over a token-by-token scan of
    ``step``: where a fault is planted in the step."""
    def run(q, k, v, la, beta, s0, n_valid=None, chunk=None):
        T = q.shape[1]
        if n_valid is not None:
            real = (jnp.arange(T) < n_valid)[None, :, None]
            beta, la = jnp.where(real, beta, 0.0), jnp.where(real, la, 0.0)

        def one(S, x):
            o, S = step(*x, S)
            return S, o

        tm = lambda a: jnp.moveaxis(a, 1, 0)    # noqa: E731
        S, o = jax.lax.scan(one, s0, tuple(tm(a) for a in (q, k, v, la,
                                                           beta)))
        return jnp.moveaxis(o, 0, 1), S
    return run


def _decay_after(q, k, v, la, beta, S):
    held = jnp.sum(S * k[..., :, None], axis=-2)
    S = S + k[..., :, None] * (beta[..., None] * (v - held))[..., None, :]
    S = jnp.exp(la)[..., None, None] * S                # decay AFTER the write
    return jnp.sum(S * q[..., :, None], axis=-2), S


def _read_after_write(q, k, v, la, beta, S):
    S = jnp.exp(la)[..., None, None] * S \
        + k[..., :, None] * (beta[..., None] * v)[..., None, :]
    held = jnp.sum(S * k[..., :, None], axis=-2)        # reads what it wrote
    S = S - k[..., :, None] * (beta[..., None] * held)[..., None, :]
    return jnp.sum(S * q[..., :, None], axis=-2), S


FAULTS = ("decay_after_correction", "beta_left_out", "q_not_scaled",
          "read_after_write", "padding_updates_the_state",
          "attention_gate_left_out")


def _plant(mp, fault, model):
    from paddle_tpu.models import gated_delta_moe_mla as kind

    real_chunked, real_gates = kind.gated_delta_chunked, kind.gates
    if fault == "decay_after_correction":
        mp.setattr(kind, "gated_delta_chunked", _recurrence_with(_decay_after))
        mp.setattr(kind, "gated_delta_step", _decay_after)
    elif fault == "read_after_write":
        mp.setattr(kind, "gated_delta_chunked",
                   _recurrence_with(_read_after_write))
        mp.setattr(kind, "gated_delta_step", _read_after_write)
    elif fault == "beta_left_out":
        def no_beta(*a):
            beta, la = real_gates(*a)
            return jnp.ones_like(beta), la
        mp.setattr(kind, "gates", no_beta)
    elif fault == "q_not_scaled":
        d = TINY["linear_key_head_dim"]
        mp.setattr(kind, "gated_delta_chunked",
                   lambda q, *a, **k: real_chunked(q * np.sqrt(d), *a, **k))
        real_step = kind.gated_delta_step
        mp.setattr(kind, "gated_delta_step",
                   lambda q, *a: real_step(q * np.sqrt(d), *a))
    elif fault == "padding_updates_the_state":
        mp.setattr(kind, "gated_delta_chunked",
                   lambda q, k, v, la, b, s0, n_valid=None, chunk=CHUNK:
                   real_chunked(q, k, v, la, b, s0, None, chunk))
    elif fault == "attention_gate_left_out":
        from paddle_tpu.models import moe_mla

        mp.setattr(moe_mla.LatentAttention, "_out",
                   lambda self, o, x: self.o_proj(o))


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(model, builder, ref, fault,
                                              monkeypatch):
    """Through the engine: a prefill of 29 tokens in a bucket of 32 (three
    padding positions) and four decode steps, against the reference."""
    steps, prompt = 4, prompt_of(29, 29)
    with monkeypatch.context() as mp:
        _plant(mp, fault, model)
        eng = make_engine(model)
        rows = capture(eng)
        req = serve(eng, prompt, steps)
    ids = prompt + [int(t) for t in req.output_tokens[:steps]]
    want = np.asarray(ref.reference_logits(builder.reference_weights(model),
                                           TINY, ids))[28:]
    res = check(ref, served_logits(rows, steps), want)
    assert not res["ok"], (fault, res)
    assert res["max_abs_diff"] > 10 * ATOL, (fault, res["max_abs_diff"])


def test_the_clamp_left_out_fails_at_weights_that_reach_it(builder, ref):
    """Gate and up projections 150 times larger: branches reach 10.  The
    program agrees with the reference; with the limit taken away from the
    program's three SwiGLUs it does not."""
    from paddle_tpu.models.llama import LlamaMLP

    model = build(builder)
    for name, p in model.named_parameters():
        if name.endswith(("gate_proj.weight", "up_proj.weight", "w_gate_up")):
            p._value = p._value * 150.0
    ids = prompt_of(19, 2)
    want = np.asarray(ref.reference_logits(builder.reference_weights(model),
                                           TINY, ids))
    assert check(ref, forward(model, ids), want)["ok"]
    model.config.swiglu_limit = None
    for layer in model.sublayers():
        if isinstance(layer, LlamaMLP):
            layer.limit = None
    res = check(ref, forward(model, ids), want)
    assert not res["ok"] and res["max_abs_diff"] > 100 * ATOL, res
