"""What a step of the gated delta-rule / gated latent-attention / held-
experts configuration (``configs/gigachat3.5-432b-a28b.json``) costs in
parameters, bytes and operations.  Beside ``roofline.py`` and the other
``roofline_*.py``, whose counts are their own models': kept with the
benchmark so that no PR that claims a gain can move the yardstick.  No JAX:
plain arithmetic over the configuration file's keys.

At the served cut (hidden 7,168; 5 layers: a delta-rule layer with the
dense SwiGLU of 18,432, then three delta-rule layers and one latent layer
with 16 held experts of 256 and one shared, width 2,048; 64 latent heads of
128 + 64 / 128 over ranks 1,536 / 512 with an output gate; 32 key / 64
value delta-rule heads of 128, convolution 4; vocabulary slice 16,032,
untied; bf16): ``latent_attention_params`` 159,844,352; ``delta_mixer_params``
235,864,320; ``expert_params`` 44,040,192; ``total_params`` 4,731,722,752
(9,463,445,504 B); ``state_bytes_per_sequence_layer`` 4,292,608 (4,194,304
of float32 state, 98,304 of convolution inputs); ``slot_bytes`` at 128 rows
2,214,985,728; ``page_bytes`` of 33,024 blocks of 16 676,331,520;
``latent_row_bytes`` 1,152 (read) in 1,280 (held).
"""

from __future__ import annotations

from typing import Dict

#: tokens a chunk of the prompt's rule takes (``ops.gated_delta.CHUNK``)
CHUNK = 64


def router_width(m: Dict) -> int:
    """The router scores ALL published experts, held or not."""
    return int((m.get("published") or {}).get("n_routed_experts",
                                              m["n_routed_experts"]))


def is_latent_layer(m: Dict, i: int) -> bool:
    return i in set(m["full_attention_layers"])


def latent_layers(m: Dict) -> int:
    return sum(is_latent_layer(m, i) for i in range(m["num_hidden_layers"]))


def delta_layers(m: Dict) -> int:
    return m["num_hidden_layers"] - latent_layers(m)


def delta_value_dim(m: Dict) -> int:
    return m["linear_num_value_heads"] * m["linear_value_head_dim"]


def delta_conv_dim(m: Dict) -> int:
    """Channels of the short convolution: ``q | k | v``."""
    return (2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
            + delta_value_dim(m))


def latent_attention_params(m: Dict) -> int:
    """Both down-projections, both up-projections, the output projection,
    the output gate and the two latent norms."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    r_q, r_kv = m["q_lora_rank"], m["kv_lora_rank"]
    v = m["v_head_dim"]
    gate = h * heads * v if m.get("gated_attention") else 0
    return (h * r_q + r_q * heads * qk + h * (r_kv + m["qk_rope_head_dim"])
            + r_kv * heads * (m["qk_nope_head_dim"] + v) + heads * v * h
            + gate + r_q + r_kv)


def delta_mixer_params(m: Dict) -> int:
    """In-projection (q | k | v | z and b | a), the depthwise convolution,
    the out-projection, ``A_log``, ``dt_bias`` and the output norm."""
    h, hv = m["hidden_size"], m["linear_num_value_heads"]
    d_v = delta_value_dim(m)
    return (h * (delta_conv_dim(m) + d_v + 2 * hv)
            + delta_conv_dim(m) * m["linear_conv_kernel_dim"] + d_v * h
            + 2 * hv + m["linear_value_head_dim"])


def expert_params(m: Dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_bytes(m: Dict, itemsize: int = 2) -> int:
    return expert_params(m) * itemsize


def dense_ffn_params(m: Dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: Dict) -> int:
    """The router's matrix and its selection bias."""
    return router_width(m) * (m["hidden_size"] + 1)


def layer_params(m: Dict, i: int) -> int:
    """Layer ``i``: its mixer, its feed-forward (the held experts and the
    shared ones) and its four norms."""
    mixer = latent_attention_params(m) if is_latent_layer(m, i) \
        else delta_mixer_params(m)
    if i < m["first_k_dense_replace"]:
        ffn = dense_ffn_params(m)
    else:
        ffn = router_params(m) + expert_params(m) * (
            m["n_routed_experts"] + m["n_shared_experts"])
    return mixer + ffn + 4 * m["hidden_size"]


def total_params(m: Dict) -> int:
    """Every layer, the embedding, the untied head and the final norm."""
    emb = m["vocab_size"] * m["hidden_size"]
    head = 0 if m.get("tie_word_embeddings") else emb
    return (sum(layer_params(m, i) for i in range(m["num_hidden_layers"]))
            + emb + head + m["hidden_size"])


def weight_bytes(m: Dict, itemsize: int = 2) -> int:
    return total_params(m) * itemsize


def state_bytes_per_sequence_layer(m: Dict, itemsize: int = 2) -> int:
    """What one live sequence holds in ONE delta-rule layer: the matrix
    state of every value head in float32 and the last ``K - 1`` inputs of
    the convolution in the pool's type."""
    state = (m["linear_num_value_heads"] * m["linear_key_head_dim"]
             * m["linear_value_head_dim"] * 4)
    return state + (m["linear_conv_kernel_dim"] - 1) * delta_conv_dim(m) \
        * itemsize


def state_bytes_per_sequence(m: Dict, itemsize: int = 2) -> int:
    return state_bytes_per_sequence_layer(m, itemsize) * delta_layers(m)


def slot_bytes(m: Dict, max_num_seqs: int, itemsize: int = 2) -> int:
    """The slot pools: a slot a row and the null slot, every delta-rule
    layer."""
    return (max_num_seqs + 1) * state_bytes_per_sequence(m, itemsize)


def latent_row_bytes(m: Dict, itemsize: int = 2) -> int:
    """One cached token of ONE latent layer, as a step READS it."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize


def page_bytes(m: Dict, num_blocks: int, block_size: int,
               itemsize: int = 2) -> int:
    """The latent layers' page pools, the row HELD in whole lane tiles of
    128 values."""
    lanes = -(-(m["kv_lora_rank"] + m["qk_rope_head_dim"]) // 128) * 128
    return num_blocks * block_size * lanes * itemsize * latent_layers(m)


def decode_state_bytes(m: Dict, rows: float, itemsize: int = 2) -> float:
    """Bytes the decode steps must move whose real rows sum to ``rows``:
    every row's state of every delta-rule layer read once and written
    once, whatever implements the step."""
    return 2.0 * rows * state_bytes_per_sequence(m, itemsize)


def decode_latent_bytes(m: Dict, kv_tokens: float, itemsize: int = 2) -> float:
    """Bytes of latent rows the decode steps must read whose rows' cache
    lengths sum to ``kv_tokens``: every row of every latent layer once."""
    return float(kv_tokens) * latent_row_bytes(m, itemsize) * latent_layers(m)


def decode_latent_flops(m: Dict, kv_tokens: float) -> float:
    """Absorbed attention over ``kv_tokens`` cached tokens: per head a
    score over the whole row and a weighted sum over the latent part, two
    operations a multiply-add, every latent layer."""
    per = 2.0 * m["num_attention_heads"] * (
        2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return per * kv_tokens * latent_layers(m)


def held_experts_bytes(m: Dict, touched: float, itemsize: int = 2) -> float:
    """Bytes the grouped matmuls must read for ``touched`` (layer, held
    expert) pairs that received a token: each once."""
    return float(touched) * expert_bytes(m, itemsize)


def held_experts_flops(m: Dict, pairs_held: float) -> float:
    """Operations of ``pairs_held`` (token, held expert) pairs."""
    return 2.0 * expert_params(m) * float(pairs_held)


def chunk_flops_per_head(m: Dict, chunk: int = CHUNK) -> float:
    """Operations of ONE chunk of ``C`` tokens of ONE value head, two a
    multiply-add (``ops/gated_delta.py``)::

        K K^T and Q K^T                    2 x 2 C^2 d_k
        the unit-lower-triangular solve    C^2 (d_k + d_v)    (U' and W)
        W S, Q S and the carry K^T U       3 x 2 C d_k d_v
        (Q K^T . D) U                      2 C^2 d_v
    """
    c, dk, dv = chunk, m["linear_key_head_dim"], m["linear_value_head_dim"]
    return float(4 * c * c * dk + c * c * (dk + dv) + 6 * c * dk * dv
                 + 2 * c * c * dv)


def chunk_flops(m: Dict, tokens: float, chunk: int = CHUNK) -> float:
    """Operations the chunked rule needs for ``tokens`` real prompt tokens
    (``tokens / C`` chunks a head, padding not counted), every value head
    of every delta-rule layer."""
    return (float(tokens) / chunk * chunk_flops_per_head(m, chunk)
            * m["linear_num_value_heads"] * delta_layers(m))


def roofline_seconds(bytes_: float, flops: float, peaks: Dict) -> float:
    """The least time the chip needs: the larger of the two bounds."""
    return max(bytes_ / peaks["bytes_per_s"], flops / peaks["flops_per_s"])
