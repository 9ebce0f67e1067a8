"""Builder for the multi-stream latent-attention / routed-expert family
(Xing4.0-29B-A4B, ``xing4_0``): the program's ``LlamaForCausalLM`` over an
``HCMoEMLAConfig`` from a configuration file's published keys, with seeded
random weights made ON THE DEVICE in the type they are served in.

It shares nothing with ``glm_moe_mla.py`` by import (that file is accepted
and stays as it is) and REPEATS its method: the placeholder swap around the
constructor, one jitted ``jax.random`` draw a parameter shape, and the
sublayers' part of ``reference_weights`` are the same lines there and
here; a later ``benchmark`` PR can fold the two (as PERF.md notes of the
three ``*_spans.py``).  What differs: ``rope_scaling`` is accepted (YaRN is
built), and the hyper-connections' parameters have draws of their own.

Every matrix, ``phi`` among them, is normal with ``INIT_STD`` 0.02; norm
scales are 1; the router's selection bias is normal with ``BIAS_STD``.
The hyper-connections are drawn so that the residual path is measurably
neither one stream nor a mean, and differs by token: with ``x~`` of unit
mean square over ``n C`` = 14,336 values a projection ``x~ phi`` has
standard deviation 0.02 sqrt(14,336) = 2.39, so gains drawn around
``GAIN_MEAN`` 0.4 (``GAIN_STD`` 0.05) put a standard deviation of about 1
of per-token variation on every coefficient's argument; ``b_pre`` and
``b_post`` are normal with ``OFFSET_STD`` 0.5; ``B_res`` is ``RES_DIAG``
2 on the diagonal plus the same noise.  The configuration file's
``assumed`` gives the spread this makes.
"""

from __future__ import annotations

from typing import Dict

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "max_position_embeddings", "rms_norm_eps",
         "rope_theta", "rope_scaling", "tie_word_embeddings", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
         "routed_scaling_factor", "norm_topk_prob", "first_k_dense_replace",
         "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
         "mhc_h_res_clamp_max")
INIT_STD = 0.02
BIAS_STD = 0.05
BIAS_NAME = "e_score_correction_bias"
GAIN_MEAN, GAIN_STD = 0.4, 0.05
OFFSET_STD = 0.5
RES_DIAG = 2.0
_DRAWS: Dict = {}       # (shape, std, type, mean) -> the jitted draw


def build(model_cfg: Dict, seed: int, dtype: str = "bfloat16"):
    # first, and before anything is made: a program without this layer kind
    # (the parent of the PR that added it) fails here, at once
    from paddle_tpu.models import HCMoEMLAConfig, LlamaForCausalLM

    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn import initializer

    if model_cfg.get("n_group", 1) != 1 or model_cfg.get("topk_group", 1) != 1:
        raise ValueError("grouped routing (n_group > 1) is not built")
    cfg = HCMoEMLAConfig(initializer_range=INIT_STD,
                         **{k: model_cfg[k] for k in _KEYS if k in model_cfg})
    served = jnp.dtype(dtype)
    before = initializer._apply_initializer
    # Layer.create_parameter looks the function up at call time.  The
    # placeholder is in the SERVED type whatever type the layer asks for:
    # every parameter is replaced below
    initializer._apply_initializer = lambda init, shape, dtype: jnp.zeros(
        tuple(int(n) for n in shape), served)
    try:
        model = LlamaForCausalLM(cfg)
    finally:
        initializer._apply_initializer = before

    f32 = jnp.dtype("float32")

    def draw(shape, std, out, mean=0.0):
        key = (shape, std, out, mean)
        if key not in _DRAWS:
            _DRAWS[key] = jax.jit(lambda k: (
                mean + jax.random.normal(k, shape, jnp.float32) * std
            ).astype(out))
        return _DRAWS[key]

    n = cfg.hc_mult
    res_mean = jnp.concatenate([jnp.zeros(2 * n, jnp.float32),
                                RES_DIAG * jnp.eye(n, dtype=jnp.float32
                                                   ).reshape(-1)])
    root = jax.random.PRNGKey(seed % (2 ** 31))
    for i, (name, p) in enumerate(model.named_parameters()):
        shape, key = tuple(p.shape), jax.random.fold_in(root, i)
        if name.endswith(BIAS_NAME):
            p._value = draw(shape, BIAS_STD, f32)(key)
        elif name.endswith("_hc.gains"):
            p._value = draw(shape, GAIN_STD, f32, GAIN_MEAN)(key)
        elif name.endswith("_hc.offsets"):
            p._value = draw(shape, OFFSET_STD, f32)(key) + res_mean
        elif len(shape) == 1:       # RMSNorm scales
            p._value = jnp.ones(shape, served)
        else:
            p._value = draw(shape, INIT_STD, served)(key)
    model.eval()
    return model


def reference_weights(model) -> Dict:
    """The served weights under the names
    ``reference/hc_moe_mla_decoder.py`` takes.  No copies: the arrays are
    the model's own."""
    named = {n: p._value for n, p in model.named_parameters()}
    layers = []
    for i in range(model.config.num_hidden_layers):
        pre = f"llama.layers.{i}."
        att = pre + "self_attn."
        w = {"in_norm": named[pre + "input_layernorm.weight"],
             "q_a": named[att + "q_a_proj.weight"],
             "q_a_norm": named[att + "q_a_layernorm.weight"],
             "q_b": named[att + "q_b_proj.weight"],
             "kv_a": named[att + "kv_a_proj_with_mqa.weight"],
             "kv_a_norm": named[att + "kv_a_layernorm.weight"],
             "kv_b": named[att + "kv_b_proj.weight"],
             "o": named[att + "o_proj.weight"],
             "post_norm": named[pre + "post_attention_layernorm.weight"]}
        for hc in ("attn_hc", "mlp_hc"):
            w[hc] = {k: named[f"{pre}{hc}.{k}"]
                     for k in ("phi", "offsets", "gains")}
        mlp = pre + "mlp."
        if mlp + "gate.weight" in named:
            w.update(router=named[mlp + "gate.weight"],
                     router_bias=named[mlp + BIAS_NAME],
                     experts_gate_up=named[mlp + "w_gate_up"],
                     experts_down=named[mlp + "w_down"],
                     shared_gate=named[mlp + "shared_experts.gate_proj.weight"],
                     shared_up=named[mlp + "shared_experts.up_proj.weight"],
                     shared_down=named[mlp + "shared_experts.down_proj.weight"])
        else:
            w.update(gate=named[mlp + "gate_proj.weight"],
                     up=named[mlp + "up_proj.weight"],
                     down=named[mlp + "down_proj.weight"])
        layers.append(w)
    return {"embed": named["llama.embed_tokens.weight"],
            "norm": named["llama.norm.weight"],
            "head": named["lm_head.weight"], "layers": layers}
