"""BERT family — bidirectional encoder with MLM head (BASELINE.json
config #2: BERT-base MLM fine-tune under DataParallel).

ref: transformer encoder layers (python/paddle/nn/layer/
transformer.py:110 TransformerEncoderLayer) — assembled here the
TPU-native way: non-causal F.scaled_dot_product_attention (Pallas flash
kernel on TPU), tp_axis metadata on every projection, static shapes.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from .. import nn
from ..base.tape import apply
from ..nn import functional as F
from ..tensor import manipulation as M

__all__ = ["BertConfig", "BertModel", "BertForMaskedLM",
           "BertForSequenceClassification"]


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dropout: float = 0.0

    @classmethod
    def tiny(cls):
        return cls(
            vocab_size=512, hidden_size=64, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128,
        )

    @classmethod
    def base(cls):
        return cls()


class BertEmbeddings(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(config.vocab_size, config.hidden_size)
        self.word_embeddings.weight.tp_axis = 0
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size
        )
        self.token_type_embeddings = nn.Embedding(
            config.type_vocab_size, config.hidden_size
        )
        self.layer_norm = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, input_ids, token_type_ids=None):
        b, s = input_ids.shape
        pos = apply(lambda: jnp.arange(s, dtype=jnp.int32)[None, :], op_name="arange")
        x = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        if token_type_ids is not None:
            x = x + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(x))


class BertLayer(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.head_dim = h // config.num_attention_heads
        self.qkv = nn.Linear(h, 3 * h)
        self.qkv.weight.tp_axis = 1
        self.attn_out = nn.Linear(h, h)
        self.attn_out.weight.tp_axis = 0
        self.attn_norm = nn.LayerNorm(h, epsilon=config.layer_norm_eps)
        self.fc1 = nn.Linear(h, config.intermediate_size)
        self.fc1.weight.tp_axis = 1
        self.fc2 = nn.Linear(config.intermediate_size, h)
        self.fc2.weight.tp_axis = 0
        self.ffn_norm = nn.LayerNorm(h, epsilon=config.layer_norm_eps)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, x, attn_mask=None):
        b, s, h = x.shape
        qkv = M.reshape(self.qkv(x), [b, s, 3, self.num_heads, self.head_dim])
        out = F.scaled_dot_product_attention(
            qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
            attn_mask=attn_mask, is_causal=False, training=self.training,
        )
        x = self.attn_norm(x + self.dropout(self.attn_out(M.reshape(out, [b, s, h]))))
        ffn = self.fc2(F.gelu(self.fc1(x)))
        return self.ffn_norm(x + self.dropout(ffn))


class BertModel(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.embeddings = BertEmbeddings(config)
        self.encoder = nn.LayerList(
            [BertLayer(config) for _ in range(config.num_hidden_layers)]
        )
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        x = self.embeddings(input_ids, token_type_ids)
        mask = None
        if attention_mask is not None:
            # [B, S] 1/0 → additive [B, 1, 1, S] (broadcasts over heads/q)
            def to_additive(m):
                return (1.0 - m.astype(jnp.float32))[:, None, None, :] * -1e9

            mask = apply(to_additive, attention_mask, op_name="attn_mask")
        for layer in self.encoder:
            x = layer(x, mask)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForMaskedLM(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.bert = BertModel(config)
        self.transform = nn.Linear(config.hidden_size, config.hidden_size)
        self.transform_norm = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.decoder = nn.Linear(config.hidden_size, config.vocab_size)
        self.decoder.weight.tp_axis = 1

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        x, _ = self.bert(input_ids, token_type_ids, attention_mask)
        x = self.transform_norm(F.gelu(self.transform(x)))
        return self.decoder(x)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


class BertForSequenceClassification(nn.Layer):
    def __init__(self, config: BertConfig, num_classes: int = 2):
        super().__init__()
        self.bert = BertModel(config)
        self.classifier = nn.Linear(config.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        return self.classifier(pooled)
