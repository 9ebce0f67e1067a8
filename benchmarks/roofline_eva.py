"""What a step of the dense decoder with chunk-summarised (EVA) attention
(``configs/evabyte-6.5b.json``) costs in parameters and bytes.  Beside
``roofline.py``, which counts every layer's keys and values as pages, and
``roofline_window_moe.py``, which reads a sliding ring: kept with the
benchmark so that no PR that claims a gain can move the yardstick.  No
JAX: plain arithmetic over the configuration file's keys
(``num_pred_heads_held`` is the stacked head's published count).

At the served sizes (hidden 4,096; 8 layers; 32 heads of 128; SwiGLU
11,008; window 2,048, chunk 16; vocabulary 320, 8 stacked heads; bf16):
``attention_params`` 67,117,056; ``mlp_params`` 135,266,304;
``layer_params`` 202,391,552 (404,783,104 B); ``total_params``
1,630,932,992 (3.26 GB); ``row_bytes`` 16,384 (a ring entry and a summary
row alike: 32 heads x 128 x 2 sides x 2 B); ``ring_bytes_per_sequence``
268,435,456 (33,554,432 a layer); ``summary_bytes_per_block`` 131,072.
"""

from __future__ import annotations

from typing import Dict


def head_dim(m: Dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def attention_params(m: Dict) -> int:
    """q, k, v, o and the two pooling vectors a head."""
    h = m["hidden_size"]
    return 4 * h * h + 2 * m["num_attention_heads"] * head_dim(m)


def mlp_params(m: Dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def layer_params(m: Dict) -> int:
    """Attention, the SwiGLU and the two norms' offsets."""
    return attention_params(m) + mlp_params(m) + 2 * m["hidden_size"]


def total_params(m: Dict) -> int:
    """Every layer, the embedding, the stacked head (all of its heads are
    held) and the final norm."""
    heads = m.get("num_pred_heads_held", m["num_pred_heads"])
    return (m["num_hidden_layers"] * layer_params(m)
            + (1 + heads) * m["vocab_size"] * m["hidden_size"]
            + m["hidden_size"])


def weight_bytes(m: Dict, itemsize: int = 2) -> int:
    return total_params(m) * itemsize


def row_bytes(m: Dict, itemsize: int = 2) -> int:
    """Keys and values of ONE ring entry, or of ONE summary row, in ONE
    layer: the two have the same shape."""
    return 2 * m["num_attention_heads"] * head_dim(m) * itemsize


def ring_bytes_per_sequence(m: Dict, itemsize: int = 2) -> int:
    """What one live sequence holds in rings over all layers, whatever its
    length."""
    return m["window_size"] * row_bytes(m, itemsize) * m["num_hidden_layers"]


def rows_per_block(m: Dict, block_size: int) -> int:
    return block_size // m["chunk_size"]


def summary_bytes_per_block(m: Dict, block_size: int, itemsize: int = 2) -> int:
    """What one block of a sequence holds in summary rows over all layers."""
    return (rows_per_block(m, block_size) * row_bytes(m, itemsize)
            * m["num_hidden_layers"])


def ring_tokens(m: Dict, position: int) -> int:
    """Ring entries a decode row at ``position`` reads in a layer."""
    return position % m["window_size"] + 1


def summary_rows(m: Dict, position: int) -> int:
    """Summary rows a decode row at ``position`` reads in a layer: every
    chunk of every CLOSED window."""
    return (m["window_size"] // m["chunk_size"]) * (position // m["window_size"])


def decode_read_bytes(m: Dict, rows: float, itemsize: int = 2) -> float:
    """Bytes the attention of decode steps must read whose rows' ring
    entries and summary rows sum to ``rows``: each once in every layer,
    whatever implements the step."""
    return float(rows) * row_bytes(m, itemsize) * m["num_hidden_layers"]


def decode_step_bytes(m: Dict, positions, itemsize: int = 2) -> float:
    """Weights once and every row's visible ring entries and summary rows:
    the least a decode step over rows at ``positions`` reads."""
    rows = sum(ring_tokens(m, p) + summary_rows(m, p) for p in positions)
    return weight_bytes(m, itemsize) + decode_read_bytes(m, rows, itemsize)
