"""Builder for the gated delta-rule / gated latent-attention / routed-expert
hybrid (GigaChat3.5-432B-A28B, ``gigachat3_5``): the program's
``LlamaForCausalLM`` over a ``GatedDeltaMoEMLAConfig`` from a configuration
file's published keys, with seeded random weights made ON THE DEVICE in the
type they are served in, the way ``hc_moe_mla.py`` makes them (the
placeholder swap around the constructor, one jitted ``jax.random`` draw a
parameter shape; it shares nothing with that file by import).

The file's ``n_routed_experts`` is how many routed experts THIS chip holds
and ``experts_held`` which; the router's width is the PUBLISHED count
(``published.n_routed_experts``).  ``vocab_size`` is the slice held here.

Draws (the file's ``assumed.seeded_weights``): every matrix normal with
``INIT_STD`` 0.02; the router's selection bias normal with ``BIAS_STD``
0.05; the ``w`` of every zero-centred norm (the four of a block, the final
one, the delta rule's output norm) normal with ``NORM_STD`` 0.02 about 0,
so no scale is exactly its initial value; the two latent norms' scales 1
(they are ``moe_mla.py``'s RMS norms); ``A_log`` the log of a uniform draw
in [1, 16]; ``dt_bias`` in the mechanism's published initial form (the
reference implementations of Gated DeltaNet and of Mamba2 before it): the
inverse softplus of ``dt``, ``dt`` log-uniform in ``DT_RANGE`` 1e-3 .. 1e-1,
so that a head's decay a token, exp(-A softplus(a + dt_bias)), spreads
and the heads of a layer keep from a third of a token to some 60 tokens
(measured at the published widths, where the gate's input ``a`` has a
standard deviation of 1.7; the file's ``assumed.seeded_weights``): in a
fifth of the heads the state a sequence holds is the sum of 17 or more
tokens' writes, and a stale slot, a wrong carry between chunks and a state
kept in too few bits then show.

``reference_weights`` also hands the reference the served slot pools
(``slot_states``), for the comparison of the state itself
(``reference/gated_delta_moe_mla_decoder.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Dict

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "max_position_embeddings", "rms_norm_eps",
         "rope_theta", "rope_scaling", "tie_word_embeddings", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
         "norm_topk_prob", "first_k_dense_replace", "linear_key_head_dim",
         "linear_value_head_dim", "linear_conv_kernel_dim",
         "linear_num_key_heads", "linear_num_value_heads",
         "linear_sigmoid_gate_scale", "linear_attn_o_norm_eps",
         "layernorm_gating_weight", "gated_attention", "swiglu_limit")
_BUILT_ONLY = (("n_group", 1), ("topk_group", 1), ("hidden_act", "silu"),
               ("attention_bias", False), ("use_shared_expert_sigmoid", False),
               ("use_mla_scaling_factor", True),
               ("norm_type", "ZeroCenteredGatedNorm"),
               ("layernorm_type", "pre_post"),
               ("linear_attention_type", "GigaChat35GatedDeltaNet"),
               ("linear_gating_type", "gated_rmsnorm_sigmoid_zero_centered"))
INIT_STD = 0.02
BIAS_STD = 0.05
NORM_STD = 0.02
DT_RANGE = (1e-3, 1e-1)
A_RANGE = (1.0, 16.0)
_DRAWS: Dict = {}       # (kind, shape, type, ...) -> the jitted draw


def model_config(model_cfg: Dict):
    """The program's configuration of what the file describes."""
    from paddle_tpu.models import GatedDeltaMoEMLAConfig

    m = model_cfg
    for key, built in _BUILT_ONLY:
        if m.get(key, built) != built:
            raise ValueError(f"{key}={m[key]!r} is not built (only {built!r})")
    routed = (m.get("published") or {}).get("n_routed_experts",
                                            m["n_routed_experts"])
    held = tuple(m.get("experts_held") or range(m["n_routed_experts"]))
    if len(held) != m["n_routed_experts"]:
        raise ValueError(f"n_routed_experts {m['n_routed_experts']} experts "
                         f"are held here, experts_held lists {len(held)}")
    return GatedDeltaMoEMLAConfig(
        initializer_range=INIT_STD, n_routed_experts=routed,
        experts_held=None if len(held) == routed else held,
        full_attention_layers=tuple(m["full_attention_layers"]),
        **{k: m[k] for k in _KEYS if k in m})


def build(model_cfg: Dict, seed: int, dtype: str = "bfloat16"):
    # first, and before anything is made: a program without this layer kind
    # (the parent of the PR that added it) fails here, at once
    cfg = model_config(model_cfg)

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.nn import initializer

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

    def normal(shape, std, out):
        key = ("normal", shape, std, out)
        if key not in _DRAWS:
            _DRAWS[key] = jax.jit(lambda k: (
                jax.random.normal(k, shape, jnp.float32) * std).astype(out))
        return _DRAWS[key]

    def log_uniform(shape):
        key = ("log_uniform", shape)
        if key not in _DRAWS:
            _DRAWS[key] = jax.jit(lambda k: jnp.log(jax.random.uniform(
                k, shape, jnp.float32, *A_RANGE)))
        return _DRAWS[key]

    def inverse_softplus_of_log_uniform(shape):
        key = ("dt_bias", shape)
        if key not in _DRAWS:
            lo, hi = (math.log(v) for v in DT_RANGE)
            _DRAWS[key] = jax.jit(lambda k: (lambda dt: dt + jnp.log(
                -jnp.expm1(-dt)))(jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, lo, hi))))
        return _DRAWS[key]

    root = jax.random.PRNGKey(seed % (2 ** 31))
    for i, (name, p) in enumerate(model.named_parameters()):
        shape, key = tuple(p.shape), jax.random.fold_in(root, i)
        if name.endswith("e_score_correction_bias"):
            p._value = normal(shape, BIAS_STD, f32)(key)
        elif name.endswith(".A_log"):
            p._value = log_uniform(shape)(key)
        elif name.endswith(".dt_bias"):
            p._value = inverse_softplus_of_log_uniform(shape)(key)
        elif name.endswith(("q_a_layernorm.weight", "kv_a_layernorm.weight")):
            p._value = jnp.ones(shape, served)
        elif len(shape) == 1:       # w of a zero-centred norm
            p._value = normal(shape, NORM_STD, served)(key)
        else:
            p._value = normal(shape, INIT_STD, served)(key)
    model.eval()
    return model


def reference_weights(model) -> Dict:
    """The served weights under the names
    ``reference/gated_delta_moe_mla_decoder.py`` takes.  No copies: the
    arrays are the model's own.  Where an engine serves ``model``, also
    ``slot_states`` (:func:`slot_states` of it) and ``slot_norms``, each
    slot's sum of squares in the first pool as it is now."""
    named = {n: p._value for n, p in model.named_parameters()}
    layers = []
    for i in range(model.config.num_hidden_layers):
        pre = f"llama.layers.{i}."
        w = {"n1": named[pre + "input_layernorm.weight"],
             "n2": named[pre + "post_mixer_layernorm.weight"],
             "n3": named[pre + "pre_ff_layernorm.weight"],
             "n4": named[pre + "post_ff_layernorm.weight"]}
        att, mix, mlp = pre + "self_attn.", pre + "delta.", pre + "mlp."
        if att + "o_proj.weight" in named:
            w.update(q_a=named[att + "q_a_proj.weight"],
                     q_a_norm=named[att + "q_a_layernorm.weight"],
                     q_b=named[att + "q_b_proj.weight"],
                     kv_a=named[att + "kv_a_proj_with_mqa.weight"],
                     kv_a_norm=named[att + "kv_a_layernorm.weight"],
                     kv_b=named[att + "kv_b_proj.weight"],
                     o=named[att + "o_proj.weight"],
                     g=named[att + "g_proj.weight"])
        else:
            w.update(in_proj=named[mix + "in_proj.weight"],
                     ba_proj=named[mix + "ba_proj.weight"],
                     conv_w=named[mix + "conv_weight"],
                     a_log=named[mix + "A_log"],
                     dt_bias=named[mix + "dt_bias"],
                     o_norm=named[mix + "o_norm"],
                     out_proj=named[mix + "out_proj.weight"])
        if mlp + "gate.weight" in named:
            w.update(router=named[mlp + "gate.weight"],
                     router_bias=named[mlp + "e_score_correction_bias"],
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
    out = {"embed": named["llama.embed_tokens.weight"],
           "norm": named["llama.norm.weight"],
           "head": named["lm_head.weight"], "layers": layers}
    if slot_states(model):
        import jax.numpy as jnp
        import numpy as np

        out["slot_states"] = functools.partial(slot_states, model)
        out["slot_norms"] = np.asarray(jnp.sum(jnp.square(
            slot_states(model)[0]), axis=(1, 2, 3)))
    return out


def slot_states(model) -> list:
    """The delta-rule layers' state pools ``[slots, H_v, d_k, d_v]``, in
    layer order, as the engine that serves ``model`` holds them NOW (the
    arrays themselves); ``[]`` where none or more than one does.  An engine
    hands its pools to nobody and the launcher gives the reference the
    model alone, so the engine is looked up among the live objects."""
    import gc

    from paddle_tpu.serving import EngineCore

    engines = [o for o in gc.get_objects()
               if isinstance(o, EngineCore) and o.model is model]
    if len(engines) != 1:
        return []
    return [pool for spec, pool in zip(engines[0].cache_specs,
                                       engines[0]._k_pools)
            if spec.state and spec.window is None]
