"""What a step of the selective-scan / attention hybrid configuration
(``configs/ai21-jamba2-3b.json``) costs in parameters and bytes.  Beside
``roofline.py``, whose counts are the dense model's (it would count 28
layers of keys and values where 2 hold any): kept with the benchmark so
that no PR that claims a gain can move the yardstick.  No JAX: plain
arithmetic over the configuration file's published keys.

At the published widths (hidden 2,560; 28 layers, 2 of attention with 20
query heads on one KV head of 128; inner width 5,120, state 16, conv 4,
dt rank 160; SwiGLU 8,192; vocabulary 65,536, tied; bf16):
``mixer_params`` 41,241,792; ``total_params`` 3,029,337,472 (6.06 GB);
``state_bytes_per_sequence`` 9,318,400 (358,400 a mixer layer);
``kv_bytes_per_token`` 1,024.
"""

from __future__ import annotations

from typing import Dict


def head_dim(m: Dict) -> int:
    return int(m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"])


def d_inner(m: Dict) -> int:
    return int(m["mamba_expand"]) * int(m["hidden_size"])


def is_attention_layer(m: Dict, i: int) -> bool:
    return i % int(m["attn_layer_period"]) == int(m["attn_layer_offset"])


def attention_layers(m: Dict) -> int:
    return sum(is_attention_layer(m, i) for i in range(m["num_hidden_layers"]))


def mixer_layers(m: Dict) -> int:
    return m["num_hidden_layers"] - attention_layers(m)


def mixer_params(m: Dict) -> int:
    """One selective-scan mixer: in-projection, depthwise conv with its
    bias, x-projection, dt-projection with its bias, the three norms,
    ``A_log``, ``D`` and the out-projection."""
    h, d, n = m["hidden_size"], d_inner(m), m["mamba_d_state"]
    r, k = m["mamba_dt_rank"], m["mamba_d_conv"]
    return (h * 2 * d + d * k + d + d * (r + 2 * n) + r * d + d
            + (r + 2 * n) + d * n + d + d * h)


def swiglu_params(m: Dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def mixer_layer_params(m: Dict) -> int:
    """The mixer, the SwiGLU and the layer's two norms."""
    return mixer_params(m) + swiglu_params(m) + 2 * m["hidden_size"]


def attention_layer_params(m: Dict) -> int:
    h, d = m["hidden_size"], head_dim(m)
    qo = 2 * h * m["num_attention_heads"] * d
    kv = 2 * h * m["num_key_value_heads"] * d
    return qo + kv + swiglu_params(m) + 2 * h


def total_params(m: Dict) -> int:
    """Every layer, the embedding (the head is tied to it) and the final
    norm."""
    emb = m["vocab_size"] * m["hidden_size"]
    head = 0 if m.get("tie_word_embeddings") else emb
    return (mixer_layers(m) * mixer_layer_params(m)
            + attention_layers(m) * attention_layer_params(m)
            + emb + head + m["hidden_size"])


def weight_bytes(m: Dict, itemsize: int = 2) -> int:
    return total_params(m) * itemsize


def state_bytes_per_sequence_layer(m: Dict, itemsize: int = 2) -> int:
    """What one live sequence holds in ONE mixer layer: the recurrent state
    in float32 and the last ``d_conv - 1`` conv inputs in the pool's type."""
    d = d_inner(m)
    return d * m["mamba_d_state"] * 4 + (m["mamba_d_conv"] - 1) * d * itemsize


def state_bytes_per_sequence(m: Dict, itemsize: int = 2) -> int:
    return state_bytes_per_sequence_layer(m, itemsize) * mixer_layers(m)


def kv_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    """Keys and values of one cached token over the ATTENTION layers."""
    return (2 * m["num_key_value_heads"] * head_dim(m) * itemsize
            * attention_layers(m))


def decode_state_bytes(m: Dict, rows: int, itemsize: int = 2) -> float:
    """Bytes the decode steps must move whose real rows sum to ``rows``:
    every row's state of every mixer layer read once and written once,
    whatever implements the step."""
    return 2.0 * rows * state_bytes_per_sequence(m, itemsize)


def scan_bytes(m: Dict, prefills: int, tokens: int, itemsize: int = 2) -> float:
    """Bytes the prefill scans must move for ``prefills`` prompts of
    ``tokens`` tokens in all: a mixer layer reads x and dt and writes y
    (each ``d_inner`` values a token, counted in the served type), and
    writes one state a prompt.  The arithmetic is elementwise: the matmul
    peak does not bound it."""
    per_token = 3 * d_inner(m) * itemsize
    return float(mixer_layers(m)) * (
        tokens * per_token
        + prefills * state_bytes_per_sequence_layer(m, itemsize))


def roofline_seconds(bytes_: float, peaks: Dict) -> float:
    return bytes_ / peaks["bytes_per_s"]
