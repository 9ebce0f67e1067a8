"""What the two files of window-layer tests share (ISSUE 36:
``test_zzzzzzzzzzzzzzzzzz_window_moe.py``, declarations, the ring, the
programs against the reference, the experts' shares;
``test_zzzzzzzzzzzzzzzzzz_window_moe_faults.py``, token identity under
preemption, reuse, a crowd and the run-ahead loop, and the planted faults):
the tiny configurations, the models and a driver that serves one request
and keeps every launch's logits.  Two files so that two test workers share
them (``--dist loadfile``)."""

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks import harness

KINDS = (["sliding_attention"] * 3 + ["full_attention"]) * 2
TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=48,
            num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=2,
            head_dim=16, max_position_embeddings=256, layer_norm_eps=1e-5,
            rope_theta=10000.0, tie_word_embeddings=True, logit_scale=1,
            sliding_window=8, layer_types=KINDS, num_experts=3,
            experts_held=[2, 3, 5], n_routed_experts=8,
            num_experts_per_tok=2, num_shared_experts=2, norm_topk_prob=True)
ATOL, RMS_REL = 1e-4, 1e-4      # float32 against float32: rounding only


@pytest.fixture(scope="module")
def builder():
    return harness.load_module("models", "window_moe")


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("reference", "window_moe_decoder")


@pytest.fixture(scope="module")
def model(builder):
    return builder.build(TINY, 7, dtype="float32")


PERIOD = dict(TINY, num_hidden_layers=4)    # one period: half the compile


@pytest.fixture(scope="module")
def period(builder):
    return builder.build(PERIOD, 7, dtype="float32")


def make_engine(model, **kw):
    from paddle_tpu.serving import EngineConfig, EngineCore, SchedulerConfig

    sched = kw.pop("scheduler", None) or SchedulerConfig(max_num_seqs=8)
    cfg = dict(num_blocks=128, block_size=4, dtype=jnp.float32,
               prefix_cache=False, scheduler=sched)
    cfg.update(kw)
    return EngineCore(model, config=EngineConfig(**cfg))


def capture(engine):
    """Every launch's program name and logits, from outside (as the
    benchmark's probe takes them)."""
    rows, orig = [], engine._step_call

    def call(program, bucket, fn, *args):
        out = orig(program, bucket, fn, *args)
        rows.append((program, np.asarray(out[1], np.float32)))
        return out

    engine._step_call = call
    return rows


def serve(engine, prompt, steps):
    from paddle_tpu.serving.request import SamplingParams

    req = engine.add_request(prompt, SamplingParams(
        max_new_tokens=steps + 1, temperature=0.0))
    for _ in range(steps + 60):
        if req.finished:
            break
        engine.step()
    assert req.finished
    return req


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"],
                                                n).tolist()


def check(ref, builder, model, rows, req, prompt, steps, cfg=TINY):
    got = np.stack([l if l.ndim == 1 else l[0] for _, l in rows])
    ids = prompt + [int(t) for t in req.output_tokens[:steps]]
    full = np.asarray(ref.reference_logits(
        builder.reference_weights(model), cfg, ids))
    return ref.compare(got, full[len(prompt) - 1:], ATOL, RMS_REL)
