"""Builder for the dense Llama-shaped family (Mistral-7B, DeepSeek-LLM):
the program's ``LlamaForCausalLM`` from a configuration file's published
keys, with seeded random weights made ON THE DEVICE in the type they are
served in.

The ``Layer`` constructor runs its own initialisers (PR 21 built float32
on the host at ~3 s a layer).  Here the default dtype is bfloat16 and,
while the constructor runs, the initialisers are swapped for a plain
``jnp.zeros`` of the shape, from outside the program; the weights served
are then the benchmark's own, drawn from ``--seed`` by one jitted
``jax.random`` call per parameter shape, so a change to the program's
initialisers cannot change the work.
"""

from __future__ import annotations

from typing import Dict

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "max_position_embeddings", "rms_norm_eps", "rope_theta",
         "tie_word_embeddings")
INIT_STD = 0.02


def build(model_cfg: Dict, seed: int):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(initializer_range=INIT_STD,
                      **{k: model_cfg[k] for k in _KEYS if k in model_cfg})
    if cfg.head_dim != int(model_cfg.get("head_dim", cfg.head_dim)):
        raise ValueError("head_dim of the file disagrees with hidden/heads")
    from paddle_tpu.nn import initializer

    before = paddle.get_default_dtype(), initializer._apply_initializer
    paddle.set_default_dtype("bfloat16")
    # Layer.create_parameter looks the function up at call time
    initializer._apply_initializer = lambda init, shape, dtype: jnp.zeros(
        tuple(int(n) for n in shape), jnp.dtype(str(dtype).split(".")[-1]))
    try:
        model = LlamaForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(before[0])
        initializer._apply_initializer = before[1]

    fns = {}

    def draw(shape):
        if shape not in fns:
            fns[shape] = jax.jit(lambda key: (
                jax.random.normal(key, shape, jnp.float32) * INIT_STD
            ).astype(jnp.bfloat16))
        return fns[shape]

    root = jax.random.PRNGKey(seed % (2 ** 31))
    for i, (name, p) in enumerate(model.named_parameters()):
        shape = tuple(p.shape)
        if len(shape) == 1:     # RMSNorm scales
            p._value = jnp.ones(shape, jnp.bfloat16)
        else:
            p._value = draw(shape)(jax.random.fold_in(root, i))
    model.eval()
    return model


def reference_weights(model) -> Dict:
    """The served weights under the names ``reference/dense_decoder.py``
    takes.  No copies: the arrays are the model's own."""
    named = {n: p._value for n, p in model.named_parameters()}
    layers = []
    for i in range(model.config.num_hidden_layers):
        pre = f"llama.layers.{i}."
        layers.append({
            "in_norm": named[pre + "input_layernorm.weight"],
            "q": named[pre + "self_attn.q_proj.weight"],
            "k": named[pre + "self_attn.k_proj.weight"],
            "v": named[pre + "self_attn.v_proj.weight"],
            "o": named[pre + "self_attn.o_proj.weight"],
            "post_norm": named[pre + "post_attention_layernorm.weight"],
            "gate": named[pre + "mlp.gate_proj.weight"],
            "up": named[pre + "mlp.up_proj.weight"],
            "down": named[pre + "mlp.down_proj.weight"]})
    return {"embed": named["llama.embed_tokens.weight"],
            "norm": named["llama.norm.weight"],
            "head": named["lm_head.weight"], "layers": layers}
