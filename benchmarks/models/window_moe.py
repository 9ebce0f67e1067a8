"""Builder for the parallel-block / window-and-global-attention / routed-
expert family (command-a-plus-05-2026, ``cohere2_moe``): the program's
``LlamaForCausalLM`` over a ``WindowMoEConfig`` from a configuration file's
published keys, with seeded random weights made ON THE DEVICE in the type
they are served in, the way ``glm_moe_mla.py`` makes them: the
constructor's initialisers are swapped for zeros from outside the program,
then one jitted ``jax.random`` call a parameter shape draws the served
weights from ``--seed``.

The file's ``num_experts`` is how many routed experts THIS chip holds and
``experts_held`` which; the router's width is the PUBLISHED count
(``n_routed_experts``).  ``layer_types`` is kept whole and its first
``num_hidden_layers`` entries are built.  Every matrix is normal with
``INIT_STD`` 0.02 and norm scales are 1 (the file's ``assumed``).
"""

from __future__ import annotations

from typing import Dict

INIT_STD = 0.02


def model_config(model_cfg: Dict):
    """The program's configuration of what the file describes."""
    from paddle_tpu.models import WindowMoEConfig

    m = model_cfg
    built_only = [
        ("expert_selection_fn", "sigmoid"), ("use_parallel_block", True),
        ("use_qk_norm", False), ("attention_bias", False),
        ("first_k_dense_replace", 0), ("rotary_pct", 1),
        ("shared_expert_combination_strategy", "average"),
        ("use_gated_activation", True), ("hidden_act", "silu")]
    for key, built in built_only:
        if m.get(key, built) != built:
            raise ValueError(f"{key}={m[key]!r} is not built (only {built!r})")
    held = tuple(m.get("experts_held") or range(m["num_experts"]))
    if len(held) != m["num_experts"]:
        raise ValueError(f"num_experts {m['num_experts']} experts are held "
                         f"here, experts_held lists {len(held)}")
    layers = m["num_hidden_layers"]
    return WindowMoEConfig(
        initializer_range=INIT_STD, vocab_size=m["vocab_size"],
        hidden_size=m["hidden_size"], intermediate_size=m["intermediate_size"],
        num_hidden_layers=layers,
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_position_embeddings=m["max_position_embeddings"],
        layer_norm_eps=m["layer_norm_eps"], rope_theta=float(m["rope_theta"]),
        tie_word_embeddings=m["tie_word_embeddings"],
        logit_scale=float(m["logit_scale"]),
        sliding_window=m["sliding_window"],
        layer_types=tuple(m["layer_types"][:layers]),
        num_routed_experts=m.get("n_routed_experts", m["num_experts"]),
        num_experts_per_tok=m["num_experts_per_tok"],
        num_shared_experts=m["num_shared_experts"],
        norm_topk_prob=m["norm_topk_prob"], experts_held=held)


def build(model_cfg: Dict, seed: int, dtype: str = "bfloat16"):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.nn import initializer

    cfg = model_config(model_cfg)
    served = jnp.dtype(dtype)
    before = initializer._apply_initializer
    # Layer.create_parameter looks the function up at call time.  The
    # placeholder is in the SERVED type whatever type the layer asks for (a
    # Layer asks for float32 unless told otherwise, and 4.7 B float32 zeros
    # are 19 GB): every parameter is replaced below
    initializer._apply_initializer = lambda init, shape, dtype: jnp.zeros(
        tuple(int(n) for n in shape), served)
    try:
        model = LlamaForCausalLM(cfg)
    finally:
        initializer._apply_initializer = before

    fns = {}

    def draw(shape):
        if shape not in fns:
            fns[shape] = jax.jit(lambda k: (
                jax.random.normal(k, shape, jnp.float32)
                * INIT_STD).astype(served))
        return fns[shape]

    root = jax.random.PRNGKey(seed % (2 ** 31))
    for i, (name, p) in enumerate(model.named_parameters()):
        shape = tuple(p.shape)
        if len(shape) == 1:         # LayerNorm scales
            p._value = jnp.ones(shape, served)
        else:
            p._value = draw(shape)(jax.random.fold_in(root, i))
    model.eval()
    return model


def reference_weights(model) -> Dict:
    """The served weights under the names
    ``reference/window_moe_decoder.py`` takes, and what of the
    configuration the reference cannot read from the file (which experts
    are held).  No copies: the arrays are the model's own."""
    named = {n: p._value for n, p in model.named_parameters()}
    layers = []
    for i in range(model.config.num_hidden_layers):
        pre = f"llama.layers.{i}."
        att, mlp = pre + "self_attn.", pre + "mlp."
        layers.append({
            "norm": named[pre + "input_layernorm.weight"],
            "q": named[att + "q_proj.weight"],
            "k": named[att + "k_proj.weight"],
            "v": named[att + "v_proj.weight"],
            "o": named[att + "o_proj.weight"],
            "router": named[mlp + "gate.weight"],
            "experts_gate_up": named[mlp + "w_gate_up"],
            "experts_down": named[mlp + "w_down"],
            "shared_gate": named[mlp + "shared_experts.gate_proj.weight"],
            "shared_up": named[mlp + "shared_experts.up_proj.weight"],
            "shared_down": named[mlp + "shared_experts.down_proj.weight"]})
    return {"embed": named["llama.embed_tokens.weight"],
            "norm": named["llama.norm.weight"], "layers": layers}
