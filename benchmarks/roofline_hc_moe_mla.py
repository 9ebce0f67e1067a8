"""What a launch of the multi-stream latent-attention / routed-expert
configuration (``configs/xing4.0-29b-a4b.json``) costs in parameters, bytes
and operations.  Beside ``roofline_moe_mla.py``, whose counts read GLM's
keys and know no hyper-connection: kept with the benchmark so that no PR
that claims a gain can move the yardstick.  No JAX: plain arithmetic over
the configuration file's published keys.

At the published widths (hidden 3,584; 4 streams; 32 heads; ranks 768 /
512; head dimensions 128 + 64 and 128; experts of width 1,024; bf16):
``attention_params`` 28,411,136; ``expert_params`` 11,010,048 (22,020,096
bytes); ``hc_params`` 344,091 a sublayer; an expert layer 744,989,046; the
dense layer 128,196,918; as served (6 layers) 4,792,669,828 parameters =
9.59 GB; ``latent_bytes_per_token`` 6,912; ``hc_bytes_per_token`` 100,352 a
sublayer.
"""

from __future__ import annotations

from typing import Dict


def latent_dim(m: Dict) -> int:
    """Values one cached token holds in one layer: ``c_kv`` and ``k_r``."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attention_params(m: Dict) -> int:
    """One layer's latent attention: W_DQ, W_UQ, W_DKV, W_UKV, W_O and the
    two norms of the latents."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * heads * qk
            + h * latent_dim(m)
            + m["kv_lora_rank"] * heads * (m["qk_nope_head_dim"]
                                           + m["v_head_dim"])
            + heads * m["v_head_dim"] * h
            + m["q_lora_rank"] + m["kv_lora_rank"])


def hc_params(m: Dict) -> int:
    """One sublayer's hyper-connection: ``phi``, the offsets, three gains."""
    n = m["hc_mult"]
    width = 2 * n + n * n
    return n * m["hidden_size"] * width + width + 3


def expert_params(m: Dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_bytes(m: Dict, itemsize: int = 2) -> int:
    return expert_params(m) * itemsize


def _outside_ffn(m: Dict) -> int:
    """Attention, the two pre-norms and the two hyper-connections."""
    return attention_params(m) + 2 * m["hidden_size"] + 2 * hc_params(m)


def expert_layer_params(m: Dict) -> int:
    """... with the router (and its selection bias), every routed expert
    and the shared ones."""
    e = m["n_routed_experts"]
    return (_outside_ffn(m) + m["hidden_size"] * e + e
            + (e + m["n_shared_experts"]) * expert_params(m))


def dense_layer_params(m: Dict) -> int:
    return _outside_ffn(m) + 3 * m["hidden_size"] * m["intermediate_size"]


def weight_params(m: Dict) -> int:
    """Everything served: the leading dense layers, the expert layers,
    embedding, untied head and the final norm."""
    dense = min(m["first_k_dense_replace"], m["num_hidden_layers"])
    emb = m["vocab_size"] * m["hidden_size"]
    head = 0 if m.get("tie_word_embeddings") else emb
    return (dense * dense_layer_params(m)
            + (m["num_hidden_layers"] - dense) * expert_layer_params(m)
            + emb + head + m["hidden_size"])


def weight_bytes(m: Dict, itemsize: int = 2) -> int:
    return weight_params(m) * itemsize


def latent_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    """Bytes one cached token holds over all layers."""
    return latent_dim(m) * itemsize * m["num_hidden_layers"]


def sublayers(m: Dict) -> int:
    """Attention and feed-forward of every layer, each behind its own
    hyper-connection."""
    return 2 * m["num_hidden_layers"]


def hc_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    """The least one sublayer's hyper-connection moves a token: the ``n``
    streams read for the coefficients and the sublayer's input, ``u``
    written; the streams and ``y`` read and the streams written for the
    way back — ``(3 n + 2) hidden`` values when each way is ONE pass."""
    return (3 * m["hc_mult"] + 2) * m["hidden_size"] * itemsize


def hc_bytes(m: Dict, tokens: float, itemsize: int = 2) -> float:
    """... over every sublayer, for ``tokens`` tokens: the work, whatever
    implements it."""
    return float(tokens) * sublayers(m) * hc_bytes_per_token(m, itemsize)


def prefill_attention_flops(m: Dict, tokens: float, tokens_sq: float
                            ) -> float:
    """The expanded prefill of prompts whose lengths sum to ``tokens`` and
    whose squared lengths sum to ``tokens_sq``, every layer: keys and
    values rebuilt from the latents (``W_UKV``), and the CAUSAL half of
    the scores and of the weighted sums; two operations a multiply-add."""
    heads = m["num_attention_heads"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    rebuild = 2.0 * m["kv_lora_rank"] * heads * (nope + v) * tokens
    core = 2.0 * heads * (nope + rope + v) * tokens_sq / 2.0
    return (rebuild + core) * m["num_hidden_layers"]


def experts_read_bytes(m: Dict, experts_touched: float,
                       itemsize: int = 2) -> float:
    """Bytes of routed-expert weights a launch must read: each expert that
    received a token, once (summed over layers by the caller's count)."""
    return float(experts_touched) * expert_bytes(m, itemsize)


def experts_flops(m: Dict, assignments: float) -> float:
    """Two operations a weight a routed (token, expert) pair:
    ``6 hidden moe_intermediate_size`` a pair."""
    return 2.0 * expert_params(m) * assignments


def roofline_seconds(bytes_: float, flops: float, peaks: Dict) -> float:
    """The least time the chip needs: the larger of the two bounds."""
    return max(bytes_ / peaks["bytes_per_s"], flops / peaks["flops_per_s"])
