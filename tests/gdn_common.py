"""What the files of gated-delta-rule tests share (ISSUE 49:
``test_zzzzzzzzzzzzzzzzzzzzzzzz_gated_delta.py``, the rule's three forms
and the reference against a naive loop; ``..._paths.py``, the layer's paths
against the reference; ``..._faults.py``, planted faults;
``..._engine.py`` and ``..._engine_check.py``, rows through ``EngineCore``): the tiny configuration as a benchmark file would state it
(hidden 64, 2 key / 4 value heads of 16, chunks of 8, 8 experts with 3
held, 5 layers in the cell's pattern), ONE model a module, a driver that
serves a request and keeps every launch's logits.  Five files so that five
test workers share them (``--dist loadfile``: the files that sort last
would otherwise run one after another at the end of the run)."""

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks import harness

TINY = dict(
    vocab_size=96, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=48, num_hidden_layers=5, num_attention_heads=4,
    num_key_value_heads=4, max_position_embeddings=512, rms_norm_eps=1e-6,
    rope_theta=10000.0, tie_word_embeddings=False, q_lora_rank=32,
    kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=3, n_shared_experts=1, num_experts_per_tok=2,
    routed_scaling_factor=2.5, norm_topk_prob=True, first_k_dense_replace=1,
    full_attention_layers=[3], linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_sigmoid_gate_scale=2, linear_attn_o_norm_eps=1e-6,
    layernorm_gating_weight=2, gated_attention=True, swiglu_limit=10,
    rope_scaling={"type": "yarn", "factor": 8.0, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 32},
    published={"n_routed_experts": 8}, experts_held=[1, 4, 6])
CHUNK = 8
ATOL, RMS_REL = 2e-4, 2e-4      # float32 against float32: rounding only


@pytest.fixture(scope="module", autouse=True)
def chunks_of_eight():
    """Prompts of tens of tokens cross chunk boundaries: the layer kind
    takes its chunk from its module's ``CHUNK`` when a program is traced."""
    from paddle_tpu.models import gated_delta_moe_mla as kind

    before, kind.CHUNK = kind.CHUNK, CHUNK
    yield
    kind.CHUNK = before


@pytest.fixture(scope="module")
def builder():
    return harness.load_module("models", "gated_delta_moe_mla")


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("reference", "gated_delta_moe_mla_decoder")


def build(builder, m=TINY, seed=7, scale=1.0):
    """The tiny model in float32; ``scale`` multiplies every
    matrix (weights at which the SwiGLU's clamp is reached)."""
    model = builder.build(m, seed, dtype="float32")
    if scale != 1.0:
        for _, p in model.named_parameters():
            if len(p.shape) > 1:
                p._value = p._value * scale
    return model


@pytest.fixture(scope="module")
def model(builder):
    return build(builder)


def make_engine(model, **kw):
    from paddle_tpu.serving import EngineConfig, EngineCore, SchedulerConfig

    sched = kw.pop("scheduler", None) or SchedulerConfig(max_num_seqs=4)
    cfg = dict(num_blocks=64, block_size=16, dtype=jnp.float32,
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


def served_logits(rows, steps):
    """The last ``steps + 1`` launches' logits: the prompt's last position
    and every decode step."""
    got = [l if l.ndim == 1 else l[0] for _, l in rows]
    return np.stack(got[-(steps + 1):])


def forward(model, ids):
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    with paddle.no_grad():
        return np.asarray(model(Tensor(jnp.asarray([ids])))._value[0])


def check(ref, got, want):
    """float32 against float32: every row compared, to rounding."""
    return ref.compare(got, want, ATOL, RMS_REL, margin_eps=0.0,
                       max_left_out_share=0.0)
