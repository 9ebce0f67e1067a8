"""Builder for the selective-scan / attention hybrid family
(AI21-Jamba2-3B, ``jamba``): the program's ``LlamaForCausalLM`` over a
``HybridMambaConfig`` from a configuration file's published keys, with
seeded random weights made ON THE DEVICE in the type they are served in,
the way ``glm_moe_mla.py`` makes them: the constructor's initialisers are
swapped for zeros from outside the program, then one jitted ``jax.random``
call a parameter shape draws the served weights from ``--seed``.

Every matrix is normal with ``INIT_STD`` 0.02 and norm scales are 1.  What
the recurrence stands on follows the published Mamba initialisation, since
with normal-0.02 values there it forgets nothing or everything and a wrong
scan would not show (the configuration file's ``assumed``): ``A_log`` =
log(1..N) in every channel and the skip ``D`` = 1, both float32;
``dt_proj.bias`` so that ``softplus(bias)`` is log-uniform in 0.001-0.1;
the depthwise convolution's weights and bias uniform in +-1/sqrt(K).
"""

from __future__ import annotations

import math
from typing import Dict

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings",
         "attn_layer_period", "attn_layer_offset", "mamba_d_state",
         "mamba_d_conv", "mamba_dt_rank", "mamba_expand", "mamba_conv_bias",
         "mamba_proj_bias")
INIT_STD = 0.02
DT_MIN, DT_MAX = 0.001, 0.1


def build(model_cfg: Dict, seed: int, dtype: str = "bfloat16"):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import HybridMambaConfig, LlamaForCausalLM
    from paddle_tpu.nn import initializer

    if model_cfg.get("num_experts", 1) != 1:
        raise ValueError("routed experts among the mixers are not built "
                         "(num_experts > 1)")
    if model_cfg.get("sliding_window") is not None:
        raise ValueError("sliding_window is not built")
    if not model_cfg.get("mamba_conv_bias", True) \
            or model_cfg.get("mamba_proj_bias", False):
        raise ValueError("only mamba_conv_bias true, mamba_proj_bias false "
                         "is seeded")
    cfg = HybridMambaConfig(initializer_range=INIT_STD,
                            **{k: model_cfg[k] for k in _KEYS
                               if k in model_cfg})
    served = jnp.dtype(dtype)
    f32 = jnp.dtype("float32")
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

    def draw(kind, shape, out):
        key = (kind, shape, out)
        if key in fns:
            return fns[key]
        if kind == "normal":
            f = lambda k: jax.random.normal(k, shape, jnp.float32) * INIT_STD
        elif kind == "conv":        # +-1/sqrt(K): K is mamba_d_conv
            b = 1.0 / math.sqrt(cfg.mamba_d_conv)
            f = lambda k: jax.random.uniform(k, shape, jnp.float32, -b, b)
        else:                       # dt bias: inverse softplus of log-uniform
            lo, hi = math.log(DT_MIN), math.log(DT_MAX)

            def f(k):
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
                return dt + jnp.log(-jnp.expm1(-dt))
        fns[key] = jax.jit(lambda k: f(k).astype(out))
        return fns[key]

    n = cfg.mamba_d_state
    a_log = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None]
    root = jax.random.PRNGKey(seed % (2 ** 31))
    for i, (name, p) in enumerate(model.named_parameters()):
        shape, key = tuple(p.shape), jax.random.fold_in(root, i)
        if name.endswith("mamba.A_log"):
            p._value = jnp.broadcast_to(a_log, shape).astype(f32)
        elif name.endswith("mamba.D"):
            p._value = jnp.ones(shape, f32)
        elif name.endswith("dt_proj.bias"):
            p._value = draw("dt_bias", shape, served)(key)
        elif name.endswith(("conv_weight", "conv_bias")):
            p._value = draw("conv", shape, served)(key)
        elif len(shape) == 1:       # RMSNorm scales
            p._value = jnp.ones(shape, served)
        else:
            p._value = draw("normal", shape, served)(key)
    model.eval()
    return model


def reference_weights(model) -> Dict:
    """The served weights under the names
    ``reference/jamba_hybrid_decoder.py`` takes.  No copies: the arrays
    are the model's own."""
    named = {n: p._value for n, p in model.named_parameters()}
    layers = []
    for i in range(model.config.num_hidden_layers):
        pre = f"llama.layers.{i}."
        w = {"in_norm": named[pre + "input_layernorm.weight"],
             "gate": named[pre + "mlp.gate_proj.weight"],
             "up": named[pre + "mlp.up_proj.weight"],
             "down": named[pre + "mlp.down_proj.weight"]}
        if pre + "mamba.in_proj.weight" in named:
            mix = pre + "mamba."
            w.update(post_norm=named[pre + "pre_ff_layernorm.weight"],
                     in_proj=named[mix + "in_proj.weight"],
                     conv_w=named[mix + "conv_weight"],
                     conv_b=named[mix + "conv_bias"],
                     x_proj=named[mix + "x_proj.weight"],
                     dt_norm=named[mix + "dt_layernorm.weight"],
                     b_norm=named[mix + "b_layernorm.weight"],
                     c_norm=named[mix + "c_layernorm.weight"],
                     dt_proj=named[mix + "dt_proj.weight"],
                     dt_bias=named[mix + "dt_proj.bias"],
                     a_log=named[mix + "A_log"], d=named[mix + "D"],
                     out_proj=named[mix + "out_proj.weight"])
        else:
            att = pre + "self_attn."
            w.update(post_norm=named[pre + "post_attention_layernorm.weight"],
                     q=named[att + "q_proj.weight"],
                     k=named[att + "k_proj.weight"],
                     v=named[att + "v_proj.weight"],
                     o=named[att + "o_proj.weight"])
        layers.append(w)
    return {"embed": named["llama.embed_tokens.weight"],
            "norm": named["llama.norm.weight"], "layers": layers}
