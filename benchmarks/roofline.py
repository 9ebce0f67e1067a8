"""Peaks of the devices, and what a step's work costs in operations and
bytes.  Kept with the benchmark so that no PR that claims a gain can move
the yardstick.  No JAX: plain arithmetic over a configuration's sizes (the
``model`` group of ``configs/<name>.json``, published key names).
"""

from __future__ import annotations

from typing import Dict, Iterable

# Published peaks per chip, keyed by ``jax.devices()[0].device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16
        "bytes_per_s": 819e9,       # HBM
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> Dict:
    """The peaks of ``device_kind``.  A device that is not in the table is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"row with its source to benchmarks/roofline.py") from None


def head_dim(m: Dict) -> int:
    return int(m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"])


def layer_matmul_params(m: Dict) -> int:
    """Weights of one decoder layer that a token is multiplied by: q, k, v
    and o projections and the three SwiGLU matrices."""
    h, d = m["hidden_size"], head_dim(m)
    q = h * m["num_attention_heads"] * d
    kv = 2 * h * m["num_key_value_heads"] * d
    o = m["num_attention_heads"] * d * h
    return q + kv + o + 3 * h * m["intermediate_size"]


def prefill_flops_sums(m: Dict, prompts: int, tokens: int,
                       tokens_sq: int) -> float:
    """Model FLOPs of prefilling ``prompts`` prompts, each alone, whose
    lengths sum to ``tokens`` and whose squared lengths to ``tokens_sq``:
    2 per weight per token through the layers, causal attention's two
    matmuls over half the square (2 * 2 * n^2 / 2 * heads * head_dim a
    layer), and the head for the LAST position only, which is all a
    prefill needs.  Padding, and logits for every position, are work the
    program may do and the model does not need: they are not counted."""
    layers, d = m["num_hidden_layers"], head_dim(m)
    per_token = 2.0 * layer_matmul_params(m) * layers
    attn = 2.0 * m["num_attention_heads"] * d * layers
    head = 2.0 * m["hidden_size"] * m["vocab_size"]
    return per_token * tokens + attn * tokens_sq + head * prompts


def prefill_flops(m: Dict, prompt_lens: Iterable[int]) -> float:
    lens = list(prompt_lens)
    return prefill_flops_sums(m, len(lens), sum(lens),
                              sum(n * n for n in lens))


def kv_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    """Bytes of keys and values one cached token holds over all layers."""
    return (2 * m["num_key_value_heads"] * head_dim(m) * itemsize
            * m["num_hidden_layers"])


def decode_kv_bytes(m: Dict, kv_tokens: int, itemsize: int = 2) -> float:
    """Bytes of cache the paged decode kernel must read for steps whose
    rows' cache lengths sum to ``kv_tokens``."""
    return float(kv_tokens) * kv_bytes_per_token(m, itemsize)


def weight_bytes(m: Dict, itemsize: int = 2) -> float:
    """Bytes of the weights as served (layers, embedding, untied head)."""
    emb = m["vocab_size"] * m["hidden_size"]
    head = 0 if m.get("tie_word_embeddings") else emb
    norms = (2 * m["num_hidden_layers"] + 1) * m["hidden_size"]
    return itemsize * (layer_matmul_params(m) * m["num_hidden_layers"]
                       + emb + head + norms)
