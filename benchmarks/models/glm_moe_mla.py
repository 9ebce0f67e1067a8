"""Builder for the latent-attention / routed-expert family (GLM-4.7-Flash,
``glm4_moe_lite``): the program's ``LlamaForCausalLM`` over a
``MoEMLAConfig`` from a configuration file's published keys, with seeded
random weights made ON THE DEVICE in the type they are served in, the way
``llama_dense.py`` makes them: the constructor's initialisers are swapped
for zeros from outside the program, then one jitted ``jax.random`` call a
parameter shape draws the served weights from ``--seed``.

Every matrix is normal with the published ``initializer_range`` 0.02,
norm scales are 1, and the router's selection bias
(``e_score_correction_bias``) is normal with ``BIAS_STD``: seeded and not
zero, so a bias that weighs instead of selecting shows, and small beside
the spread of the scores, so no expert is starved.
"""

from __future__ import annotations

from typing import Dict

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "max_position_embeddings", "rms_norm_eps",
         "rope_theta", "tie_word_embeddings", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
         "routed_scaling_factor", "norm_topk_prob", "first_k_dense_replace")
INIT_STD = 0.02
BIAS_STD = 0.05
BIAS_NAME = "e_score_correction_bias"


def build(model_cfg: Dict, seed: int, dtype: str = "bfloat16"):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import LlamaForCausalLM, MoEMLAConfig
    from paddle_tpu.nn import initializer

    if model_cfg.get("n_group", 1) != 1 or model_cfg.get("topk_group", 1) != 1:
        raise ValueError("grouped routing (n_group > 1) is not built")
    if model_cfg.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not built (ROADMAP: YaRN)")
    cfg = MoEMLAConfig(initializer_range=INIT_STD,
                       **{k: model_cfg[k] for k in _KEYS if k in model_cfg})
    served = jnp.dtype(dtype)
    before = initializer._apply_initializer
    # Layer.create_parameter looks the function up at call time.  The
    # placeholder is in the SERVED type whatever type the layer asks for (a
    # Layer asks for float32 unless told otherwise, and 4.5 B float32 zeros
    # are 18 GB): every parameter is replaced below
    initializer._apply_initializer = lambda init, shape, dtype: jnp.zeros(
        tuple(int(n) for n in shape), served)
    try:
        model = LlamaForCausalLM(cfg)
    finally:
        initializer._apply_initializer = before

    fns = {}

    def draw(shape, std, out):
        key = (shape, std, out)
        if key not in fns:
            fns[key] = jax.jit(lambda k: (
                jax.random.normal(k, shape, jnp.float32) * std).astype(out))
        return fns[key]

    root = jax.random.PRNGKey(seed % (2 ** 31))
    for i, (name, p) in enumerate(model.named_parameters()):
        shape, key = tuple(p.shape), jax.random.fold_in(root, i)
        if name.endswith(BIAS_NAME):
            p._value = draw(shape, BIAS_STD, jnp.dtype("float32"))(key)
        elif len(shape) == 1:       # RMSNorm scales
            p._value = jnp.ones(shape, served)
        else:
            p._value = draw(shape, INIT_STD, served)(key)
    model.eval()
    return model


def reference_weights(model) -> Dict:
    """The served weights under the names ``reference/moe_mla_decoder.py``
    takes.  No copies: the arrays are the model's own."""
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
