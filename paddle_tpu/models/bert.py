"""BERT model family (ladder config #3: BERT-base SQuAD finetune, DP×8).

Capability analog of PaddleNLP's BERT stack targeted by the reference's
capability ladder.  TPU-first: plain dense layers (the DP-over-8 config needs
no TP), batch sharded over ``dp`` by the data pipeline; attention goes
through the same fused-attention dispatcher as Llama.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import run_op
from ..nn import functional as F
from ..nn.common import Dropout, Embedding, Linear
from ..nn.container import LayerList
from ..nn.initializer import Normal
from ..nn.layers import Layer
from ..nn.norm import LayerNorm
from ..parallel.utils import sharding_constraint


@dataclass
class BertConfig:
    """BERT-base defaults."""

    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    pad_token_id: int = 0

    @classmethod
    def tiny(cls, **kw):
        defaults = dict(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                        num_attention_heads=2, intermediate_size=64,
                        max_position_embeddings=64, type_vocab_size=2)
        defaults.update(kw)
        return cls(**defaults)


class BertEmbeddings(Layer):
    """word + position + token-type embeddings, LayerNorm, dropout."""

    def __init__(self, config: BertConfig):
        super().__init__()
        init = Normal(0.0, config.initializer_range)
        self.word_embeddings = Embedding(config.vocab_size, config.hidden_size,
                                         weight_attr=init)
        self.position_embeddings = Embedding(config.max_position_embeddings,
                                             config.hidden_size, weight_attr=init)
        self.token_type_embeddings = Embedding(config.type_vocab_size,
                                               config.hidden_size, weight_attr=init)
        self.layer_norm = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None):
        from .. import tensor as ops

        S = input_ids.shape[1]
        pos = ops.arange(0, S, dtype="int64")
        x = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        if token_type_ids is None:
            # reference semantics: default token_type_ids = zeros, so
            # segment-0 embeddings are ALWAYS added (not skipped)
            x = x + self.token_type_embeddings.weight[0]
        else:
            x = x + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(x))


class BertSelfAttention(Layer):
    """Bidirectional multi-head attention with additive padding mask."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.num_heads = config.num_attention_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        h = config.hidden_size
        init = Normal(0.0, config.initializer_range)
        self.q_proj = Linear(h, h, weight_attr=init)
        self.k_proj = Linear(h, h, weight_attr=init)
        self.v_proj = Linear(h, h, weight_attr=init)
        self.out_proj = Linear(h, h, weight_attr=init)
        self.dropout = Dropout(config.attention_probs_dropout_prob)

    def forward(self, x, attention_mask=None):
        B, S = x.shape[0], x.shape[1]
        n, d = self.num_heads, self.head_dim
        q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)

        def attn(qv, kv, vv, *mask):
            qh = qv.reshape(B, S, n, d).transpose(0, 2, 1, 3)
            kh = kv.reshape(B, S, n, d).transpose(0, 2, 1, 3)
            vh = vv.reshape(B, S, n, d).transpose(0, 2, 1, 3)
            logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                                preferred_element_type=jnp.float32)
            logits = logits / math.sqrt(d)
            if mask:
                m = mask[0]  # [B, S] 1=token 0=pad
                bias = (1.0 - m[:, None, None, :].astype(logits.dtype)) * -1e9
                logits = logits + bias
            probs = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vh.dtype), vh)
            return out.transpose(0, 2, 1, 3).reshape(B, S, n * d)

        args = [q, k, v]
        if attention_mask is not None:
            args.append(attention_mask)
        ctx = run_op("bert_attention", attn, *args)
        return self.out_proj(ctx)


class BertLayer(Layer):
    """Post-norm transformer encoder block (original BERT residual order)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        init = Normal(0.0, config.initializer_range)
        self.attention = BertSelfAttention(config)
        self.attn_norm = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.linear1 = Linear(config.hidden_size, config.intermediate_size,
                              weight_attr=init)
        self.linear2 = Linear(config.intermediate_size, config.hidden_size,
                              weight_attr=init)
        self.ffn_norm = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x, attention_mask=None):
        x = sharding_constraint(x, "dp")
        h = self.attn_norm(x + self.dropout(self.attention(x, attention_mask)))
        ff = self.linear2(F.gelu(self.linear1(h)))
        return self.ffn_norm(h + self.dropout(ff))


class BertPooler(Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.dense = Linear(config.hidden_size, config.hidden_size,
                            weight_attr=Normal(0.0, config.initializer_range))

    def forward(self, hidden):
        from .. import tensor as ops

        return ops.tanh(self.dense(hidden[:, 0]))


class BertModel(Layer):
    """Embeddings + encoder stack + pooler (PaddleNLP ``BertModel`` analog)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.embeddings = self._build_embeddings(config)
        self.encoder = LayerList(
            [BertLayer(config) for _ in range(config.num_hidden_layers)])
        self.pooler = BertPooler(config)

    def _build_embeddings(self, config):
        """Overridable factory (ERNIE swaps in task-type embeddings)."""
        return BertEmbeddings(config)

    @staticmethod
    def _pad_default_mask(input_ids, pad_token_id):
        """Reference default mask: pad_token_id positions are masked out
        (PaddleNLP semantics; HF defaults to all-ones instead)."""
        from .. import tensor as ops

        return ops.not_equal(
            input_ids, ops.full_like(input_ids, pad_token_id)
        ).astype("float32")

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        if attention_mask is None:
            attention_mask = self._pad_default_mask(
                input_ids, self.config.pad_token_id)
        h = self.embeddings(input_ids, token_type_ids)
        for layer in self.encoder:
            h = layer(h, attention_mask)
        return h, self.pooler(h)


class BertForSequenceClassification(Layer):
    def __init__(self, config: BertConfig, num_classes: int = 2):
        super().__init__()
        self.bert = BertModel(config)
        self.dropout = Dropout(config.hidden_dropout_prob)
        self.classifier = Linear(config.hidden_size, num_classes,
                                 weight_attr=Normal(0.0, config.initializer_range))

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        return self.classifier(self.dropout(pooled))


class BertForQuestionAnswering(Layer):
    """SQuAD head: start/end span logits (the capability-ladder finetune)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.bert = BertModel(config)
        self.qa_outputs = Linear(config.hidden_size, 2,
                                 weight_attr=Normal(0.0, config.initializer_range))

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        seq, _ = self.bert(input_ids, token_type_ids, attention_mask)
        logits = self.qa_outputs(seq)          # [B, S, 2]
        from .. import tensor as ops

        start, end = ops.split(logits, 2, axis=-1)
        return ops.squeeze(start, -1), ops.squeeze(end, -1)
