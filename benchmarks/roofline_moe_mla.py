"""What a step of the latent-attention / routed-expert configuration
(``configs/glm-4.7-flash.json``) costs in parameters, bytes and
operations.  Beside ``roofline.py``, whose counts are the dense model's
(``layer_matmul_params`` and ``kv_bytes_per_token`` read
``num_key_value_heads``): kept with the benchmark so that no PR that claims
a gain can move the yardstick.  No JAX: plain arithmetic over the
configuration file's published keys.

At the published widths (hidden 2,048; 20 heads; ranks 768 / 512; head
dimensions 192 + 64 and 256; experts of width 1,536; bf16):
``attention_params`` 21,757,952; ``expert_params`` 9,437,184 (18,874,368
bytes); ``latent_bytes_per_token_layer`` 1,152.
"""

from __future__ import annotations

from typing import Dict


def latent_dim(m: Dict) -> int:
    """Values one cached token holds in one layer: ``c_kv`` and ``k_r``."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attention_params(m: Dict) -> int:
    """Matrices of one layer's latent attention: W_DQ, W_UQ, W_DKV, W_UKV,
    W_O."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * heads * qk
            + h * latent_dim(m)
            + m["kv_lora_rank"] * heads * (m["qk_nope_head_dim"]
                                           + m["v_head_dim"])
            + heads * m["v_head_dim"] * h)


def expert_params(m: Dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_bytes(m: Dict, itemsize: int = 2) -> int:
    return expert_params(m) * itemsize


def expert_layer_params(m: Dict) -> int:
    """Attention, router, every routed expert and the shared ones."""
    return (attention_params(m) + m["hidden_size"] * m["n_routed_experts"]
            + (m["n_routed_experts"] + m["n_shared_experts"])
            * expert_params(m))


def dense_layer_params(m: Dict) -> int:
    return attention_params(m) + 3 * m["hidden_size"] * m["intermediate_size"]


def weight_bytes(m: Dict, itemsize: int = 2) -> int:
    """Matrices as served: the leading dense layers, the expert layers,
    embedding and untied head (norm scales and the router's bias, a few
    thousand values, are left out)."""
    dense = min(m["first_k_dense_replace"], m["num_hidden_layers"])
    emb = m["vocab_size"] * m["hidden_size"]
    head = 0 if m.get("tie_word_embeddings") else emb
    return itemsize * (dense * dense_layer_params(m)
                       + (m["num_hidden_layers"] - dense)
                       * expert_layer_params(m) + emb + head)


def latent_bytes_per_token_layer(m: Dict, itemsize: int = 2) -> int:
    return latent_dim(m) * itemsize


def latent_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    """Bytes one cached token holds over all layers."""
    return latent_bytes_per_token_layer(m, itemsize) * m["num_hidden_layers"]


def decode_latent_bytes(m: Dict, kv_tokens: int, itemsize: int = 2) -> float:
    """Bytes of latent cache the decode steps must read whose rows' cache
    lengths sum to ``kv_tokens``: every row of every layer once, whatever
    implements the attention."""
    return float(kv_tokens) * latent_bytes_per_token(m, itemsize)


def decode_latent_flops(m: Dict, kv_tokens: int) -> float:
    """Absorbed attention over ``kv_tokens`` cached tokens: per head a
    score over the whole row and a weighted sum over the latent part, two
    operations a multiply-add, every layer."""
    per = 2.0 * m["num_attention_heads"] * (latent_dim(m) + m["kv_lora_rank"])
    return per * kv_tokens * m["num_hidden_layers"]


def experts_read_bytes(m: Dict, experts_touched: int,
                       itemsize: int = 2) -> float:
    """Bytes of routed-expert weights a launch must read: each expert that
    received a token, once (summed over layers by the caller's count)."""
    return float(experts_touched) * expert_bytes(m, itemsize)


def experts_flops(m: Dict, assignments: int) -> float:
    """Two operations a weight a routed (token, expert) pair."""
    return 2.0 * expert_params(m) * assignments


def roofline_seconds(bytes_: float, flops: float, peaks: Dict) -> float:
    """The least time the chip needs: the larger of the two bounds."""
    return max(bytes_ / peaks["bytes_per_s"], flops / peaks["flops_per_s"])
