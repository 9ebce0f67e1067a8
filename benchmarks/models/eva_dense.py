"""Builder for the dense decoder with chunk-summarised (EVA) attention
(EvaByte, ``evabyte``): the program's ``LlamaForCausalLM`` over an
``EvaConfig`` from a configuration file's published keys, with seeded
random weights made ON THE DEVICE in the type they are served in, the way
``window_moe.py`` makes them: the constructor's initialisers are swapped
for zeros from outside the program, then one jitted ``jax.random`` call a
parameter shape draws the served weights from ``--seed``.

Every matrix is normal with ``INIT_STD`` 0.02, the two pooling vectors a
head (``adaptive_mu_k``, ``adaptive_phi``) normal with ``head_dim ** -0.5``
and the norms' offsets 0 (the file's ``assumed``).  The file's
``num_pred_heads`` is the heads COMPUTED (1, the next byte) and
``num_pred_heads_held`` the published count: the stacked head is held
whole and the program computes head 0.
"""

from __future__ import annotations

from typing import Dict

INIT_STD = 0.02


def model_config(model_cfg: Dict):
    """The program's configuration of what the file describes."""
    from paddle_tpu.models import EvaConfig

    m = model_cfg
    built_only = [
        ("attention_class", "eva"), ("attention_bias", False),
        ("hidden_act", "silu"), ("norm_add_unit_offset", True),
        ("fp32_skip_add", True), ("fp32_logits", True), ("fp32_ln", False),
        ("mixedp_attn", True), ("rope_scaling", None),
        ("tie_word_embeddings", False)]
    for key, built in built_only:
        if m.get(key, built) != built:
            raise ValueError(f"{key}={m[key]!r} is not built (only {built!r})")
    return EvaConfig(
        initializer_range=INIT_STD, vocab_size=m["vocab_size"],
        hidden_size=m["hidden_size"], intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        window_size=m["window_size"], chunk_size=m["chunk_size"],
        num_pred_heads=m.get("num_pred_heads_held", m["num_pred_heads"]))


def build(model_cfg: Dict, seed: int, dtype: str = "bfloat16"):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.nn import initializer

    cfg = model_config(model_cfg)
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

    fns = {}

    def draw(shape, std):
        if (shape, std) not in fns:
            fns[shape, std] = jax.jit(lambda k: (
                jax.random.normal(k, shape, jnp.float32) * std).astype(served))
        return fns[shape, std]

    root = jax.random.PRNGKey(seed % (2 ** 31))
    for i, (name, p) in enumerate(model.named_parameters()):
        shape = tuple(p.shape)
        if len(shape) == 1:         # a norm's offset
            p._value = jnp.zeros(shape, served)
        else:
            std = cfg.head_dim ** -0.5 if ".adaptive_" in name else INIT_STD
            p._value = draw(shape, std)(jax.random.fold_in(root, i))
    model.eval()
    return model


def reference_weights(model) -> Dict:
    """The served weights under the names ``reference/eva_decoder.py``
    takes.  No copies: the arrays are the model's own."""
    named = {n: p._value for n, p in model.named_parameters()}
    layers = []
    for i in range(model.config.num_hidden_layers):
        pre = f"llama.layers.{i}."
        att, mlp = pre + "self_attn.", pre + "mlp."
        layers.append({
            "norm1": named[pre + "input_layernorm.weight"],
            "q": named[att + "q_proj.weight"],
            "k": named[att + "k_proj.weight"],
            "v": named[att + "v_proj.weight"],
            "o": named[att + "o_proj.weight"],
            "mu": named[att + "adaptive_mu_k"],
            "phi": named[att + "adaptive_phi"],
            "norm2": named[pre + "post_attention_layernorm.weight"],
            "gate": named[mlp + "gate_proj.weight"],
            "up": named[mlp + "up_proj.weight"],
            "down": named[mlp + "down_proj.weight"]})
    return {"embed": named["llama.embed_tokens.weight"],
            "norm": named["llama.norm.weight"],
            "head": named["lm_head.weight"], "layers": layers}
