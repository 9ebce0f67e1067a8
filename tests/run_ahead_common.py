"""What the two files of run-ahead tests share (ISSUE 35:
``test_zzzzzzzzzzzzzzzzz_run_ahead.py``, the tokens;
``test_zzzzzzzzzzzzzzzzz_run_ahead_rules.py``, the rules): the tiny models of
the three layer kinds, one arrival schedule, and a driver that serves it with
the loop's step or the bare one.  Two files so that two test workers share
them (``--dist loadfile``)."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks import harness
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.serving import (
    EngineConfig,
    EngineCore,
    SamplingParams,
    SchedulerConfig,
)

BS = 4
KINDS = ("llama", "moe_mla", "mamba_hybrid")
SEEDED = dict(temperature=0.8, top_k=20, top_p=0.9, seed=1234)
# one sampling an arrival, in turn: greedy rows and seeded rows share launches
MIXED = ({}, SEEDED)
TINY_MOE = dict(
    vocab_size=320, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=48, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=4, max_position_embeddings=256, rms_norm_eps=1e-5,
    rope_theta=10000.0, tie_word_embeddings=False, q_lora_rank=32,
    kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=20,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    routed_scaling_factor=1.8, norm_topk_prob=True, first_k_dense_replace=1)
TINY_SSM = dict(
    vocab_size=320, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=1,
    max_position_embeddings=512, rms_norm_eps=1e-6, tie_word_embeddings=True,
    attn_layer_period=2, attn_layer_offset=1, mamba_d_state=8,
    mamba_d_conv=4, mamba_dt_rank=8, mamba_expand=2, mamba_conv_bias=True,
    mamba_proj_bias=False)


@pytest.fixture(scope="module")
def models():
    """One tiny float32 model a layer kind, built when first asked for."""
    built = {}

    def get(kind):
        if kind not in built:
            if kind == "llama":
                paddle.seed(0)
                built[kind] = LlamaForCausalLM(
                    LlamaConfig.tiny(num_hidden_layers=1))
            elif kind == "moe_mla":
                built[kind] = harness.load_module(
                    "models", "glm_moe_mla").build(TINY_MOE, 7,
                                                   dtype="float32")
            else:
                built[kind] = harness.load_module(
                    "models", "jamba_hybrid").build(TINY_SSM, 7,
                                                    dtype="float32")
        return built[kind]

    return get


def make_engine(model, kind="llama", max_num_seqs=8, num_blocks=128, **kw):
    sched = kw.pop("scheduler", None) or SchedulerConfig(
        max_num_seqs=max_num_seqs)
    cfg = dict(num_blocks=num_blocks, block_size=BS, dtype=jnp.float32,
               prefix_cache=kind == "llama", scheduler=sched)
    cfg.update(kw)
    return EngineCore(model, config=EngineConfig(**cfg),
                      registry=MetricsRegistry())


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(1, 200, n).tolist()


# (step at which it arrives, prompt, max_new_tokens): three at once, which
# end by length at different steps so the row bucket shrinks 4 -> 2 -> 1,
# then three more that grow it again and join rows already in flight
ARRIVALS = ([(0, prompt_of(9 + 2 * i, i), n)
             for i, n in enumerate((4, 9, 14))]
            + [(12 + i, prompt_of(6 + 3 * i, 10 + i), n)
               for i, n in enumerate((7, 3, 11))])


def drive(engine, ahead, arrivals=ARRIVALS, sampling=None, between=None):
    """Serve ``arrivals`` with the loop's step (``ahead``) or the bare one;
    ``sampling`` is one dict for every request or a tuple taken in turn;
    ``between(n, reqs)`` runs between two steps, as the serving loop's
    intake does.  Returns the requests in arrival order."""
    step = engine.step_ahead if ahead else engine.step
    todo = sorted(arrivals, key=lambda a: a[0])
    kinds = sampling if isinstance(sampling, tuple) else (sampling or {},)
    reqs, n = [], 0
    while todo or engine.scheduler.has_work():
        while todo and todo[0][0] <= n:
            _, prompt, new = todo.pop(0)
            reqs.append(engine.add_request(prompt, SamplingParams(
                max_new_tokens=new, **kinds[len(reqs) % len(kinds)])))
        if between is not None:
            between(n, reqs)
        if engine.scheduler.has_work():
            step()
            if not ahead:
                assert engine._inflight is None
        n += 1
        assert n < 2000
    assert all(r.finished for r in reqs)
    assert engine._inflight is None
    return reqs


def outputs(reqs):
    return [list(r.output_tokens) for r in reqs]


def ahead_counts(engine):
    c = engine._ahead_counters
    return {"launches": int(c["launches"].value),
            "dropped": int(c["dropped_rows"].value),
            "settles": {k: int(v.value) for k, v in c["settles"].items()}}


def assert_clean(engine):
    """Nothing in flight, no block and no slot held, and every token the
    scheduler planned was run (a dropped row included)."""
    assert engine._inflight is None
    assert engine.kv.occupancy() == 0.0
    assert engine.kv.state_slots_held == 0
    assert engine.scheduler.tokens_planned == \
        engine.stepprof.scheduled_tokens()
