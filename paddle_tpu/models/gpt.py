"""GPT family — decoder-only LM with learned positions (BASELINE.json
config #4: GPT-3-13B hybrid TP+PP+DP).

ref: the reference trains GPT via PaddleNLP's gpt modeling (downstream
of this repo); in-repo counterparts are the transformer layers
(python/paddle/nn/layer/transformer.py) and fleet's TP layers this
model's tp_axis metadata targets (fleet/layers/mpu/mp_layers.py).

TPU-native notes, same design rules as models/llama.py:
- attention lowers to F.scaled_dot_product_attention → Pallas flash
  attention on TPU;
- all projections carry ``tp_axis`` so hybrid placement shards them
  (column-parallel qkv/fc1, row-parallel proj/fc2);
- static shapes, no data-dependent control flow — jit/scan friendly.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import numpy as np

from .. import nn
from ..base import random as _random
from ..base.tensor import Tensor
from ..nn import functional as F
from ..tensor import manipulation as M

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM"]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 1024
    layer_norm_epsilon: float = 1e-5
    dropout: float = 0.0

    @classmethod
    def tiny(cls):
        return cls(
            vocab_size=512, hidden_size=64, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128,
        )

    @classmethod
    def gpt3_13b(cls):
        return cls(
            vocab_size=50304, hidden_size=5120, intermediate_size=20480,
            num_hidden_layers=40, num_attention_heads=40,
            max_position_embeddings=2048,
        )


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_attention_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        h = config.hidden_size
        self.qkv_proj = nn.Linear(h, 3 * h)
        self.out_proj = nn.Linear(h, h)
        self.qkv_proj.weight.tp_axis = 1  # column parallel
        self.out_proj.weight.tp_axis = 0  # row parallel
        self.dropout = config.dropout

    def forward(self, x, cache=None, cur_len=None):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)  # [B, S, 3H]
        qkv = M.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]
        if cache is None:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=self.dropout,
                training=self.training,
            )
            return self.out_proj(M.reshape(out, [b, s, h]))

        from ..base.tape import apply
        from ..ops.paged_attention import PagedLayerCache

        if isinstance(cache, PagedLayerCache):
            from ..ops.paged_attention import paged_attention_step

            if self.training and self.dropout > 0 and s == 1:
                raise ValueError(
                    "the paged KV decode path has no attention-probability "
                    "dropout (the dense cache path does) — call "
                    "model.eval() before paged-cache generation"
                )
            if s == 1:
                out, new_cache = paged_attention_step(
                    q, k, v, cache, cur_len, 1)
                return self.out_proj(M.reshape(out, [b, s, h])), new_cache

            q, kc, vc, mask, new_cache = paged_attention_step(
                q, k, v, cache, cur_len, s)
            out = F.scaled_dot_product_attention(
                q, kc, vc, attn_mask=mask, is_causal=False,
                dropout_p=self.dropout, training=self.training,
            )
            return self.out_proj(M.reshape(out, [b, s, h])), new_cache

        from .generation import update_kv_cache

        k_cache, v_cache = cache

        def step(kk, vv, kc, vc, cl):
            return update_kv_cache(kk, vv, kc, vc, cl, s)

        k_cache, v_cache, mask = apply(
            step, k, v, k_cache, v_cache, cur_len, op_name="kv_cache_update"
        )
        out = F.scaled_dot_product_attention(
            q, k_cache, v_cache, attn_mask=mask, is_causal=False,
            dropout_p=self.dropout, training=self.training,
        )
        return self.out_proj(M.reshape(out, [b, s, h])), (k_cache, v_cache)


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.fc1 = nn.Linear(config.hidden_size, config.intermediate_size)
        self.fc2 = nn.Linear(config.intermediate_size, config.hidden_size)
        self.fc1.weight.tp_axis = 1
        self.fc2.weight.tp_axis = 0
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, x, cache=None, cur_len=None):
        if cache is None:
            x = x + self.attn(self.ln_1(x))
        else:
            attn_out, cache = self.attn(self.ln_1(x), cache=cache, cur_len=cur_len)
            x = x + attn_out
        h = self.fc2(F.gelu(self.fc1(self.ln_2(x))))
        out = x + self.dropout(h)
        return out if cache is None else (out, cache)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size)
        self.wte.weight.tp_axis = 0  # vocab parallel
        self.wpe = nn.Embedding(config.max_position_embeddings, config.hidden_size)
        self.h = nn.LayerList([GPTBlock(config) for _ in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.drop = nn.Dropout(config.dropout)

    def forward(self, input_ids, caches=None, cur_len=None):
        b, s = input_ids.shape
        import jax.numpy as jnp

        from ..base.tape import apply

        if caches is None:
            pos = apply(lambda: jnp.arange(s, dtype=jnp.int32)[None, :], op_name="arange")
        else:
            # cur_len: a scalar (generate()) or per-sequence [B] starts
            # (the serving engine's ragged batches) -> [1|B, s]. The
            # clamp only touches the engine's PADDED lanes (a chunk tail
            # past max_len, an idle slot): an out-of-range take() would
            # fill them with NaN rather than garbage.
            last = self.config.max_position_embeddings - 1
            pos = apply(
                lambda cl: jnp.minimum(
                    jnp.reshape(cl, (-1, 1)).astype(jnp.int32)
                    + jnp.arange(s, dtype=jnp.int32)[None, :], last),
                cur_len, op_name="arange_offset",
            )
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        if caches is None:
            for block in self.h:
                x = block(x)
            return self.ln_f(x)
        new_caches = []
        for block, cache in zip(self.h, caches):
            x, cache = block(x, cache=cache, cur_len=cur_len)
            new_caches.append(cache)
        return self.ln_f(x), new_caches


class GPTForCausalLM(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.transformer = GPTModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)
        self.lm_head.weight.tp_axis = 1

    def forward(self, input_ids):
        return self.lm_head(self.transformer(input_ids))

    def init_cache(self, batch: int, max_len: int, dtype=None,
                   block_size=None, num_blocks=None, tables=None,
                   kv_dtype=None):
        """Dense caches by default; ``block_size`` switches to the paged
        (block-table) layout (ops/paged_attention.py) — same protocol as
        LlamaForCausalLM.init_cache (incl. ``kv_dtype="int8"``)."""
        c = self.config
        dt = dtype or self.transformer.wte.weight.dtype
        head_dim = c.hidden_size // c.num_attention_heads
        if block_size is not None:
            from ..ops.paged_attention import alloc_paged_kv_caches

            return alloc_paged_kv_caches(
                c.num_hidden_layers, batch, max_len, c.num_attention_heads,
                head_dim, dt, block_size=block_size, num_blocks=num_blocks,
                tables=tables, kv_dtype=kv_dtype,
            )
        if kv_dtype is not None:
            raise ValueError(
                "kv_dtype quantization requires the paged cache "
                "(pass block_size)")
        from .generation import alloc_kv_caches

        return alloc_kv_caches(
            c.num_hidden_layers, batch, max_len, c.num_attention_heads,
            head_dim, dt,
        )

    def forward_with_cache(self, input_ids, caches, cur_len):
        h, caches = self.transformer(input_ids, caches=caches, cur_len=cur_len)
        return self.lm_head(h), caches

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len: int) -> float:
        n = self.num_params()
        c = self.config
        attn = 12 * c.num_hidden_layers * c.hidden_size * seq_len
        return 6 * n + attn
