"""What a step of the parallel-block / window-and-global-attention /
held-experts configuration (``configs/command-a-plus-05-2026.json``) costs
in parameters and bytes.  Beside ``roofline.py``, which counts every
layer's keys and values as pages, and ``roofline_moe_mla.py``, which reads
latent rows and every expert: kept with the benchmark so that no PR that
claims a gain can move the yardstick.  No JAX: plain arithmetic over the
configuration file's keys (``num_experts`` is the experts HELD here).

At the served sizes (hidden 4,096; 4 layers, 3 of them sliding_attention
with a window of 4,096; 128 query heads on 8 key/value heads of 128; 16
held experts of 128, width 4,096, 4 shared; vocabulary slice 32,768, tied;
bf16): ``attention_params`` 142,606,336; ``expert_params`` 50,331,648
(``expert_bytes`` 100,663,296); ``layer_params`` 1,149,767,680;
``total_params`` 4,733,292,544 (9.47 GB); ``ring_bytes_per_sequence``
50,331,648 (16,777,216 a window layer); ``page_bytes_per_token`` 4,096.
"""

from __future__ import annotations

from typing import Dict


def layer_kinds(m: Dict):
    return list(m["layer_types"][:m["num_hidden_layers"]])


def window_layers(m: Dict) -> int:
    return sum(k == "sliding_attention" for k in layer_kinds(m))


def global_layers(m: Dict) -> int:
    return m["num_hidden_layers"] - window_layers(m)


def attention_params(m: Dict) -> int:
    h, d = m["hidden_size"], m["head_dim"]
    return (2 * h * m["num_attention_heads"] * d
            + 2 * h * m["num_key_value_heads"] * d)


def expert_params(m: Dict) -> int:
    """One expert (routed or shared): gate, up and down."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_bytes(m: Dict, itemsize: int = 2) -> int:
    return expert_params(m) * itemsize


def router_params(m: Dict) -> int:
    """The router scores ALL published experts, held or not."""
    return m["hidden_size"] * m.get("n_routed_experts", m["num_experts"])


def layer_params(m: Dict) -> int:
    """Attention, the one norm, the router, the shared experts and the
    routed experts HELD here."""
    return (attention_params(m) + m["hidden_size"] + router_params(m)
            + (m["num_shared_experts"] + m["num_experts"]) * expert_params(m))


def total_params(m: Dict) -> int:
    """Every layer, the embedding slice (the head is tied to it) and the
    final norm."""
    emb = m["vocab_size"] * m["hidden_size"]
    head = 0 if m.get("tie_word_embeddings") else emb
    return (m["num_hidden_layers"] * layer_params(m) + emb + head
            + m["hidden_size"])


def weight_bytes(m: Dict, itemsize: int = 2) -> int:
    return total_params(m) * itemsize


def kv_row_bytes(m: Dict, itemsize: int = 2) -> int:
    """Keys and values of ONE token in ONE layer."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def ring_bytes_per_sequence_layer(m: Dict, itemsize: int = 2) -> int:
    """What one live sequence holds in ONE window layer, whatever its
    length: a ring of ``sliding_window`` tokens' keys and values."""
    return m["sliding_window"] * kv_row_bytes(m, itemsize)


def ring_bytes_per_sequence(m: Dict, itemsize: int = 2) -> int:
    return ring_bytes_per_sequence_layer(m, itemsize) * window_layers(m)


def page_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    """Keys and values of one cached token over the GLOBAL layers: what a
    token holds in pages."""
    return kv_row_bytes(m, itemsize) * global_layers(m)


def window_decode_bytes(m: Dict, window_tokens: int, itemsize: int = 2
                        ) -> float:
    """Bytes the window layers of decode steps must read whose rows'
    ``min(length, window)`` sum to ``window_tokens``: every such ring entry
    once in every window layer, whatever implements the step."""
    return float(window_tokens) * kv_row_bytes(m, itemsize) * window_layers(m)


def global_decode_bytes(m: Dict, kv_tokens: int, itemsize: int = 2) -> float:
    """Bytes the global layers of decode steps must read whose rows'
    cache lengths sum to ``kv_tokens``."""
    return float(kv_tokens) * page_bytes_per_token(m, itemsize)


def held_experts_bytes(m: Dict, touched: float, itemsize: int = 2) -> float:
    """Bytes the grouped matmuls must read for ``touched`` (layer, held
    expert) pairs that received a token: each such expert once."""
    return float(touched) * expert_bytes(m, itemsize)


def held_experts_flops(m: Dict, pairs_held: float) -> float:
    """Operations of ``pairs_held`` (token, held expert) pairs."""
    return 2.0 * expert_params(m) * float(pairs_held)


def roofline_seconds(bytes_: float, flops: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of the two."""
    return max(bytes_ / peaks["bytes_per_s"], flops / peaks["flops_per_s"])
