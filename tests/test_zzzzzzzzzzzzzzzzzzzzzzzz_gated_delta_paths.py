"""The gated delta-rule layer kind's paths against the benchmark's plain
reference (ISSUE 49): the cache-less forward, prefill, chunked prefill and
prefill-then-decode through ``EngineCore`` (logits), and the sixteen shares
of an expert layer against the uncut layer.  float32 on the CPU at toy
widths (``gdn_common.py``)."""

import numpy as np
import pytest

import jax.numpy as jnp

from gdn_common import (TINY, builder, capture, check, chunks_of_eight,
                        forward, make_engine, model, prompt_of, ref, serve,
                        served_logits)     # noqa: F401  (fixtures)


# --- the model's paths against the reference -----------------------------------

def test_the_cache_less_forward_agrees_with_the_reference(model, builder, ref):
    ids = prompt_of(45, 1)
    want = np.asarray(ref.reference_logits(builder.reference_weights(model),
                                           TINY, ids))
    res = check(ref, forward(model, ids), want)
    assert res["ok"] and res["rows"] == 45, res


@pytest.mark.parametrize("n,budget", [
    (29, None),     # one-shot prefill, ends inside a chunk and a bucket
    (64, None),     # whole chunks, a whole bucket
    (45, 16),       # chunked prefill: carried launches of 16, 16 and 13
])
def test_prefill_then_decode_agrees_with_the_reference(model, builder, ref,
                                                       n, budget):
    from paddle_tpu.serving import SchedulerConfig

    steps = 6
    eng = make_engine(model, scheduler=SchedulerConfig(
        max_num_seqs=4, max_prefill_tokens_per_step=budget))
    rows = capture(eng)
    prompt = prompt_of(n, n)
    req = serve(eng, prompt, steps)
    programs = [p for p, _ in rows]
    assert programs.count("decode") == steps
    assert ("chunk" in programs) == (budget is not None)
    ids = prompt + [int(t) for t in req.output_tokens[:steps]]
    want = np.asarray(ref.reference_logits(builder.reference_weights(model),
                                           TINY, ids))[n - 1:]
    res = check(ref, served_logits(rows, steps), want)
    assert res["ok"] and res["rows"] == steps + 1, res


# --- the shares of an expert layer ------------------------------------------------

def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Sixteen chips of two experts each: what every share gives for its
    own experts, with the shared expert (which every chip computes alike)
    counted ONCE, is what a layer holding all 32 gives -- through the
    clamped SwiGLU."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import GatedDeltaMoEMLAConfig, RoutedExperts

    def layer(held):
        return RoutedExperts(GatedDeltaMoEMLAConfig.tiny(
            n_routed_experts=32, num_experts_per_tok=4, experts_held=held))

    paddle.seed(5)
    whole = layer(None)
    whole.e_score_correction_bias.set_value(jnp.asarray(
        np.random.default_rng(2).normal(0, 0.05, (32,)), jnp.float32))
    x = Tensor(jnp.asarray(np.random.default_rng(9).normal(
        size=(2, 11, 64)) * 30, jnp.float32))      # some branches reach 10
    with paddle.no_grad():
        want = np.asarray(whole(x)._value)
        shared = np.asarray(whole.shared_experts(x)._value)
        total, loads = np.zeros_like(want), []
        for chip in range(16):
            held = (2 * chip, 2 * chip + 1)
            part = layer(held)
            sel = jnp.asarray(held)
            part.gate.weight.set_value(whole.gate.weight._value)
            part.e_score_correction_bias.set_value(
                whole.e_score_correction_bias._value)
            part.w_gate_up.set_value(whole.w_gate_up._value[sel])
            part.w_down.set_value(whole.w_down._value[sel])
            for name in ("gate_proj", "up_proj", "down_proj"):
                getattr(part.shared_experts, name).weight.set_value(
                    getattr(whole.shared_experts, name).weight._value)
            total += np.asarray(part(x)._value) - shared
            loads.append(np.asarray(part.load))
    assert np.abs(total + shared - want).max() < 2e-5
    assert all((l == loads[0]).all() for l in loads)    # one router
    assert int(loads[0].sum()) == 2 * 11 * 4
    assert np.abs(want - shared).max() > 1e-3           # the experts matter
