"""MiniCPM-SALA-family decoder LM (``model_type`` minicpm_sala) —
training path.

The stack is DECLARED by a published list, ``mixer_types``: layer ``i``
mixes its tokens by block-sparse softmax attention where it says
``"minicpm4"`` and by a lightning linear attention where it says
``"lightning-attn"`` (8 : 24 over the published 32, no period rule);
every MLP is a dense SwiGLU. One block, with the muP scalings::

    h = x + c Mixer(Norm(x));   y = h + c MLP(Norm(h))
    c = scale_depth / sqrt(num_hidden_layers)        the PUBLISHED depth
    Norm(x) = x rsqrt(mean x^2 + eps) g              float32 inside
    x_0 = scale_emb Embed(ids)
    logits = Head(Norm(y_L) / (hidden_size / dim_model_base))

Lightning attention (``LightningAttention``; a = the normed stream)::

    q, k, v = a W_q, a W_k, a W_v                    heads of d each
    q, k <- RoPE(Norm_d(q)), RoPE(Norm_d(k))         one gain [d] each,
                                                     half-split, whole head
    S_t = lam_h S_{t-1} + k_t v_t^T;  o_t = S_t^T q_t / sqrt(d)
            lam_h = exp(-2^(-8 (h + 1) / heads))     ops/lightning_attention.py
    out = (Norm_H(merge(o)) * sigmoid(a W_g)) W_o

Block-sparse attention (``BlockSparseAttention``)::

    q = a W_q (heads of d), k, v = a W_k, a W_v (kv heads of d)
    q, k <- Norm_d(q), Norm_d(k);  NO RoPE
    table = select_blocks(q, k)      no gradient  ops/sparse_attention.py
    out = (merge(block_sparse_attention(q, k, v, table)) * sigmoid(a W_g)) W_o

A model may hold a SHARE of what is published, as ``models/afmoe.py``:
``held_layers`` layers from ``first_layer`` on and ``vocab_rows`` rows of
the untied embedding and head. Nothing is recomputed in the backward
beyond the elementwise steps around the kernels (``jax.checkpoint``
below): the one cell that runs it fits so (PERF.md section 4).

Not here: the published kernels' two-stage approximation of the
selection's log-sum-exp and their dense path for short sequences (every
query runs the rule; a query with at most ``topk`` valid blocks sees all
of them, which IS dense attention). **Training forward only: no cache,
no engine** (ROADMAP M4, M16).

The plain float32 reference of the same equations is
``chipbench/families/minicpm_sala_reference.py``;
``tests/test_minicpm_sala.py`` holds the two together.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..base.tape import apply
from ..base.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from .decoder import CausalLM, DecoderStack, SwiGLU, held_share, rope

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_PUBLISHED_MIXERS = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LIGHTNING
    for i in range(32))


@dataclass
class MiniCPMSALAConfig:
    """The published keys by their published names (MiniCPM-SALA's
    values), the selection's ``sparse_config``, then what of it a model
    holds."""
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: Optional[Sequence[str]] = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    sparse_config: dict = field(default_factory=lambda: dict(
        kernel_size=32, kernel_stride=16, block_size=64, topk=64,
        init_blocks=1, window_size=2048))
    # the share held here (default: everything)
    first_layer: int = 0
    held_layers: Optional[int] = None
    vocab_rows: Optional[int] = None

    def __post_init__(self):
        if self.mixer_types is None:
            if self.num_hidden_layers != len(_PUBLISHED_MIXERS):
                raise ValueError("mixer_types names every published layer")
            self.mixer_types = list(_PUBLISHED_MIXERS)
        if len(self.mixer_types) != self.num_hidden_layers:
            raise ValueError("mixer_types names every published layer")
        if set(self.mixer_types) - {SPARSE, LIGHTNING}:
            raise ValueError(f"mixer_types {set(self.mixer_types)}")
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError("lightning attention with grouped keys is not "
                             "built (published: 32 of each)")
        held_share(self)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.num_hidden_layers)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                    num_hidden_layers=4,
                    mixer_types=[SPARSE, LIGHTNING, LIGHTNING, LIGHTNING],
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16, lightning_nh=2, lightning_nkv=2,
                    lightning_head_dim=32, dim_model_base=16,
                    sparse_config=dict(kernel_size=32, kernel_stride=16,
                                       block_size=64, topk=3, init_blocks=1,
                                       window_size=64))
        base.update(kw)
        return MiniCPMSALAConfig(**base)


def head_norm(x, g, eps: float, heads: int, theta: Optional[float] = None):
    """x [B, S, heads * d] -> [B, S, heads, d]: RMSNorm over each head's
    d dims with the one gain ``g`` [d], then (``theta``) RoPE on the whole
    head; float32 inside."""
    b, s, width = x.shape
    f = x.astype(jnp.float32).reshape(b, s, heads, width // heads)
    f = f * jax.lax.rsqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + eps)
    f = f * g.astype(jnp.float32)
    if theta is not None:
        f = rope(f, theta)
    return f.astype(x.dtype)


def lightning_gate(o, z, g, eps: float):
    """``Norm_H(merge(o)) g sigmoid(z)``: o [B, S, heads, d], z [B, S,
    heads * d] -> [B, S, heads * d]; float32 inside."""
    f = o.astype(jnp.float32).reshape(z.shape)
    f = f * jax.lax.rsqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + eps)
    f = f * g.astype(jnp.float32) * jax.nn.sigmoid(z.astype(jnp.float32))
    return f.astype(o.dtype)


def _gain(layer, size):
    return layer.create_parameter([size], default_initializer=I.Constant(1.0))


class LightningAttention(nn.Layer):
    """The linear-attention mixer (module docstring). Training forward
    only: the recurrence's state is kept nowhere."""

    def __init__(self, config: MiniCPMSALAConfig):
        super().__init__()
        c = config
        self.heads, self.d = c.lightning_nh, c.lightning_head_dim
        self.eps, self.theta = c.rms_norm_eps, float(c.rope_theta)
        h, width = c.hidden_size, self.heads * self.d
        self.q_proj = nn.Linear(h, width, bias_attr=False)
        self.k_proj = nn.Linear(h, width, bias_attr=False)
        self.v_proj = nn.Linear(h, width, bias_attr=False)
        self.z_proj = nn.Linear(h, width, bias_attr=False)
        self.o_proj = nn.Linear(width, h, bias_attr=False)
        self.q_norm_weight = _gain(self, self.d)
        self.k_norm_weight = _gain(self, self.d)
        self.o_norm_weight = _gain(self, width)

    def forward(self, a):
        from ..ops.lightning_attention import (alibi_slopes,
                                               lightning_attention)
        from ..tensor import manipulation as M

        b, s = a.shape[0], a.shape[1]
        with jax.named_scope("lin.project"):
            q, k, v, z = (self.q_proj(a), self.k_proj(a), self.v_proj(a),
                          self.z_proj(a))
        with jax.named_scope("lin.norm_rope"):
            turned = jax.checkpoint(functools.partial(
                head_norm, eps=self.eps, heads=self.heads, theta=self.theta))
            q = apply(turned, q, self.q_norm_weight, op_name="head_norm")
            k = apply(turned, k, self.k_norm_weight, op_name="head_norm")
            v = M.reshape(v, [b, s, self.heads, self.d])
        with jax.named_scope("lin.scan"):
            o = apply(lambda q, k, v: lightning_attention(
                q, k, v, alibi_slopes(self.heads)), q, k, v,
                op_name="lightning_attention")
        with jax.named_scope("lin.gate"):
            out = apply(
                jax.checkpoint(functools.partial(lightning_gate,
                                                 eps=self.eps)),
                o, z, self.o_norm_weight, op_name="lightning_gate")
            return self.o_proj(out)


class BlockSparseAttention(nn.Layer):
    """The sparse softmax mixer (module docstring). ``blocks_chosen`` and
    ``query_rows`` hold, on the device, the blocks the LAST forward's
    table named and the (token, kv group) rows it named them for,
    ``band_blocks`` how many of those block reads the kernels' band pass
    served, once a query block (0 where the shapes keep it from
    engaging) (int32: a sum over a run's steps would wrap). Training
    forward only."""

    def __init__(self, config: MiniCPMSALAConfig):
        super().__init__()
        c = config
        self.nq, self.nkv, self.d = (c.num_attention_heads,
                                     c.num_key_value_heads, c.head_dim)
        self.eps, self.rule = c.rms_norm_eps, dict(c.sparse_config)
        # the band the rule forces, as the kernels are told it
        self.band = dict(init_blocks=self.rule["init_blocks"],
                         window_blocks=(self.rule["window_size"]
                                        // self.rule["block_size"]))
        h, q, k = c.hidden_size, self.nq * self.d, self.nkv * self.d
        self.q_proj = nn.Linear(h, q, bias_attr=False)
        self.k_proj = nn.Linear(h, k, bias_attr=False)
        self.v_proj = nn.Linear(h, k, bias_attr=False)
        self.z_proj = nn.Linear(h, q, bias_attr=False)
        self.o_proj = nn.Linear(q, h, bias_attr=False)
        self.q_norm_weight = _gain(self, self.d)
        self.k_norm_weight = _gain(self, self.d)
        for name in ("blocks_chosen", "query_rows", "band_blocks"):
            self.register_buffer(name, Tensor(jnp.zeros([], jnp.int32),
                                              _internal=True))

    def forward(self, a, routing=None):
        """``routing``: a list that is given the table, block ids [B, kv
        groups, S, topk]."""
        from ..ops.sparse_attention import (band_blocks,
                                            block_sparse_attention,
                                            select_blocks)
        from ..tensor import manipulation as M

        b, s = a.shape[0], a.shape[1]
        with jax.named_scope("sparse.project"):
            q = apply(jax.checkpoint(functools.partial(
                head_norm, eps=self.eps, heads=self.nq)),
                self.q_proj(a), self.q_norm_weight, op_name="head_norm")
            k = apply(jax.checkpoint(functools.partial(
                head_norm, eps=self.eps, heads=self.nkv)),
                self.k_proj(a), self.k_norm_weight, op_name="head_norm")
            v = M.reshape(self.v_proj(a), [b, s, self.nkv, self.d])
            z = self.z_proj(a)
        with jax.named_scope("sparse.select"):
            table = apply(lambda q, k: select_blocks(q, k, **self.rule),
                          q.detach(), k.detach(), op_name="select_blocks")
        self.blocks_chosen.set_value(
            jnp.sum(table._data >= 0, dtype=jnp.int32))
        self.query_rows.set_value(
            jnp.asarray(math.prod(table.shape[:3]), jnp.int32))
        self.band_blocks.set_value(jnp.asarray(
            b * self.nkv * band_blocks(s, table.shape[3], **self.band),
            jnp.int32))
        if routing is not None:
            routing.append(table)
        with jax.named_scope("sparse.attend"):
            o = apply(functools.partial(block_sparse_attention, **self.band),
                      q, k, v, table, op_name="block_sparse_attention")
        with jax.named_scope("sparse.gate"):
            out = M.reshape(o, [b, s, self.nq * self.d]) * F.sigmoid(z)
            return self.o_proj(out)


class MiniCPMSALADecoderLayer(nn.Layer):
    """One block around the mixer its ``mixer_type`` names."""

    def __init__(self, config: MiniCPMSALAConfig, mixer_type: str):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.mixer_type, self.scale = mixer_type, config.residual_scale
        self.sparse = mixer_type == SPARSE
        if self.sparse:
            self.self_attn = BlockSparseAttention(config)
        else:
            self.linear_attn = LightningAttention(config)
        self.mlp = SwiGLU(h, config.intermediate_size)
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)

    def forward(self, x, routing=None):
        """``routing``: a list that is given a sparse block's table,
        block ids [B, kv groups, S, topk]."""
        a = self.input_layernorm(x)
        mixed = (self.self_attn(a, routing) if self.sparse
                 else self.linear_attn(a))
        h = x + self.scale * mixed
        with jax.named_scope("mlp.dense"):
            return h + self.scale * self.mlp(self.post_attention_layernorm(h))


class MiniCPMSALAModel(DecoderStack):
    def __init__(self, config: MiniCPMSALAConfig):
        super().__init__(
            config,
            lambda i: MiniCPMSALADecoderLayer(config, config.mixer_types[i]),
            multiplier=config.scale_emb)


class MiniCPMSALAForCausalLM(CausalLM):
    """The decoder with its untied head over the held rows of the
    vocabulary."""

    def __init__(self, config: MiniCPMSALAConfig):
        super().__init__(config, MiniCPMSALAModel(config))

    def forward(self, input_ids, routing=None):
        """``routing``: a list that is given every sparse block's table,
        [B, kv groups, S, topk], in order (a train step may return
        them)."""
        c = self.config
        return self.lm_head(self.model(input_ids, routing)
                            * (c.dim_model_base / c.hidden_size))

    def sparse_layers(self):
        return [layer.self_attn for layer in self.model.layers
                if layer.sparse]

    def blocks_chosen(self):
        """[sparse blocks] int32 on the device: the blocks the last
        forward's tables named."""
        return self.stacked(self.sparse_layers(), "blocks_chosen")

    def query_rows(self):
        """[sparse blocks] int32: the (token, kv group) rows they named
        them for."""
        return self.stacked(self.sparse_layers(), "query_rows")

    def band_blocks(self):
        """[sparse blocks] int32: the block reads of ``blocks_chosen``
        that the kernels' band pass served."""
        return self.stacked(self.sparse_layers(), "band_blocks")
