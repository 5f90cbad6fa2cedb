"""Granite-4.0-H-family decoder LM (``model_type`` granitemoehybrid with
no routed experts) — training path.

The stack is DECLARED by a published list, ``layer_types``: layer ``i``
mixes its tokens by a Mamba-2 state-space layer where it says ``"mamba"``
and by grouped-query softmax attention WITHOUT any position term where it
says ``"attention"`` (36 : 4 over the published 40, a period of ten);
every MLP is a dense SwiGLU (``shared_intermediate_size``;
``num_local_experts`` 0). One block, with Granite's four multipliers::

    h = x + r Mixer(Norm(x));   y = h + r MLP(Norm(h))     r = residual_multiplier
    Norm(x) = x rsqrt(mean x^2 + eps) g                    float32 inside
    x_0 = embedding_multiplier Embed(ids)
    logits = (Norm(y_L) Embed^T) / logits_scaling          the head is TIED

Mamba-2 (``Mamba2Mixer``; a = the normed stream; H heads of P, one group
of state size N)::

    [z | xBC | dt] = a W_in                       d_inner + (d_inner + 2 N) + H
    xBC = silu(conv(xBC) + b_conv)                causal, depthwise, ops/conv_silu.py
    [x | B | C] = xBC;    dt = softplus(dt + dt_bias);    A = -exp(A_log)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D_h x_t
                                                  ops/mamba2_ssd.py
    out = Norm_g(y * silu(z)) W_out               the gate BEFORE the norm,
                                                  one norm over all of d_inner

Attention (``NoPEAttention``)::

    q = a W_q (heads of d), k, v = a W_k, a W_v (kv heads of d); no bias,
    no rotary or other position term; causal softmax(q k^T * attention_multiplier)
    out = merge(o) W_o                            ops/flash_attention.py

A model may hold a SHARE of what is published, as ``models/minicpm_sala.py``:
``held_layers`` layers from ``first_layer`` on and ``vocab_rows`` rows of
the tied embedding. Each block's MLP half is computed again in the
backward (``fleet.utils.recompute``), and the elementwise steps around
the kernels keep their inputs alone (``jax.checkpoint``): the one cell
that runs it fits so (PERF.md section 4).

``Mamba2Mixer.chunk_carry`` holds, on the device, the LAST forward's mean
over heads and over the sequence's chunks of ``mamba_chunk_size``
consecutive tokens of ``exp(sum of dt_t A_h over the chunk)``: the share
of a head's state that survives a published chunk.

**Training forward only: no cache, no engine** (ROADMAP M4).

The plain float32 reference of the same equations is
``chipbench/families/granite_hybrid_reference.py``;
``tests/test_granite_hybrid.py`` holds the two together.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..base.tape import apply
from ..base.tensor import Tensor
from ..nn import initializer as I
from .decoder import CausalLM, DecoderStack, SwiGLU, held_share

MAMBA, ATTENTION = "mamba", "attention"


@dataclass
class GraniteHybridConfig:
    """The published keys by their published names (granite-4.0-h-micro's
    values), then what of it a model holds."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: Optional[Sequence[str]] = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    # the share held here (default: everything)
    first_layer: int = 0
    held_layers: Optional[int] = None
    vocab_rows: Optional[int] = None

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = [ATTENTION if i % 10 == 5 else MAMBA
                                for i in range(self.num_hidden_layers)]
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every published layer")
        if set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if self.mamba_n_groups != 1:
            raise ValueError("Mamba-2 with more than one group of B and C "
                             "is not built (published: 1)")
        if (self.mamba_expand * self.hidden_size
                != self.mamba_n_heads * self.mamba_d_head):
            raise ValueError("mamba_expand x hidden_size is not "
                             "mamba_n_heads x mamba_d_head")
        held_share(self)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=64,
                    shared_intermediate_size=96, num_hidden_layers=4,
                    layer_types=[MAMBA, ATTENTION, MAMBA, MAMBA],
                    num_attention_heads=4, num_key_value_heads=2,
                    mamba_n_heads=2, mamba_d_head=64, mamba_d_state=128,
                    mamba_chunk_size=128)
        base.update(kw)
        return GraniteHybridConfig(**base)


def ssm_rates(dt, dt_bias):
    """``softplus(dt + dt_bias)``: dt [B, S, H] -> float32."""
    return jax.nn.softplus(dt.astype(jnp.float32)
                           + dt_bias.astype(jnp.float32))


def ssm_gate(y, z, g, eps: float):
    """``Norm_g(y * silu(z))``: y, z [B, S, H P] -> [B, S, H P]; float32
    inside."""
    f = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    f = f * jax.lax.rsqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + eps)
    return (f * g.astype(jnp.float32)).astype(y.dtype)


def chunk_carry(rates, a, chunk: int):
    """The mean over heads and whole chunks of ``chunk`` tokens of
    ``exp(sum of rates_t a_h over the chunk)``: rates [B, S, H] -> []."""
    b, s, h = rates.shape
    whole = s // chunk * chunk
    sums = jnp.sum((rates * a)[:, :whole].reshape(b, -1, chunk, h), axis=2)
    return jnp.mean(jnp.exp(sums))


class Mamba2Mixer(nn.Layer):
    """The state-space mixer (module docstring). Training forward only:
    the convolution's and the recurrence's state are kept nowhere."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        c = config
        self.heads, self.n = c.mamba_n_heads, c.mamba_d_state
        self.inner = self.heads * c.mamba_d_head
        self.conv_dim = self.inner + 2 * self.n
        self.eps, self.chunk = c.rms_norm_eps, c.mamba_chunk_size
        self.in_proj = nn.Linear(
            c.hidden_size, self.inner + self.conv_dim + self.heads,
            bias_attr=False)
        self.out_proj = nn.Linear(self.inner, c.hidden_size, bias_attr=False)

        def leaf(shape, value):
            return self.create_parameter(
                shape, default_initializer=I.Constant(value))

        self.conv1d_weight = self.create_parameter(
            [c.mamba_d_conv, self.conv_dim],
            default_initializer=I.Normal(0.0, 0.02))
        self.conv1d_bias = leaf([self.conv_dim], 0.0)
        self.dt_bias = leaf([self.heads], 0.0)
        self.A_log = leaf([self.heads], 0.0)
        self.D = leaf([self.heads], 1.0)
        self.norm_weight = leaf([self.inner], 1.0)
        self.register_buffer("chunk_carry", Tensor(
            jnp.zeros([], jnp.float32), _internal=True))

    def forward(self, a):
        from ..ops.conv_silu import conv_silu
        from ..ops.mamba2_ssd import ssd

        inner, n = self.inner, self.n

        def scan(xbc, rates, a_log, d):
            return ssd(xbc[:, :, :inner], rates,
                       -jnp.exp(a_log.astype(jnp.float32)),
                       xbc[:, :, inner:inner + n], xbc[:, :, inner + n:],
                       d.astype(jnp.float32))

        with jax.named_scope("ssm.project"):
            zxbcdt = self.in_proj(a)
        with jax.named_scope("ssm.conv"):
            xbc = apply(conv_silu, zxbcdt[:, :, inner:inner + self.conv_dim],
                        self.conv1d_weight, self.conv1d_bias,
                        op_name="conv_silu")
        with jax.named_scope("ssm.scan"):
            rates = apply(jax.checkpoint(ssm_rates),
                          zxbcdt[:, :, inner + self.conv_dim:], self.dt_bias,
                          op_name="ssm_rates")
            self.chunk_carry.set_value(chunk_carry(
                rates._data, -jnp.exp(self.A_log._data.astype(jnp.float32)),
                self.chunk))
            y = apply(scan, xbc, rates, self.A_log, self.D, op_name="ssd")
        with jax.named_scope("ssm.gate"):
            out = apply(
                jax.checkpoint(functools.partial(ssm_gate, eps=self.eps)),
                y, zxbcdt[:, :, :inner], self.norm_weight, op_name="ssm_gate")
            return self.out_proj(out)


class NoPEAttention(nn.Layer):
    """Grouped-query causal attention with no position term and an
    explicit softmax scale. Training forward only."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        c = config
        self.nq, self.nkv, self.d = (c.num_attention_heads,
                                     c.num_key_value_heads, c.head_dim)
        self.scale = float(c.attention_multiplier)
        h, q, k = c.hidden_size, self.nq * self.d, self.nkv * self.d
        self.q_proj = nn.Linear(h, q, bias_attr=False)
        self.k_proj = nn.Linear(h, k, bias_attr=False)
        self.v_proj = nn.Linear(h, k, bias_attr=False)
        self.o_proj = nn.Linear(q, h, bias_attr=False)

    def forward(self, a):
        from ..ops.flash_attention import flash_attention
        from ..tensor import manipulation as M

        b, s = a.shape[0], a.shape[1]
        with jax.named_scope("attn.project"):
            q = M.reshape(self.q_proj(a), [b, s, self.nq, self.d])
            k = M.reshape(self.k_proj(a), [b, s, self.nkv, self.d])
            v = M.reshape(self.v_proj(a), [b, s, self.nkv, self.d])
        with jax.named_scope("attn.full"):
            o = apply(lambda q, k, v: flash_attention(q, k, v, True,
                                                      self.scale),
                      q, k, v, op_name="flash_attention")
        with jax.named_scope("attn.project"):
            return self.o_proj(M.reshape(o, [b, s, self.nq * self.d]))


class GraniteHybridDecoderLayer(nn.Layer):
    """One block around the mixer its ``layer_type`` names."""

    def __init__(self, config: GraniteHybridConfig, layer_type: str):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.layer_type, self.scale = layer_type, config.residual_multiplier
        if layer_type == MAMBA:
            self.mamba = Mamba2Mixer(config)
        else:
            self.self_attn = NoPEAttention(config)
        self.shared_mlp = SwiGLU(h, config.shared_intermediate_size)
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)

    def forward(self, x):
        from ..distributed.fleet.utils.recompute import recompute

        mlp, norm, scale = (self.shared_mlp, self.post_attention_layernorm,
                            self.scale)

        def mlp_half(h):     # closes over its own two layers alone
            with jax.named_scope("mlp.dense"):
                return h + scale * mlp(norm(h))

        a = self.input_layernorm(x)
        mixed = (self.mamba(a) if self.layer_type == MAMBA
                 else self.self_attn(a))
        return recompute(mlp_half, x + scale * mixed)


class GraniteHybridModel(DecoderStack):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__(
            config,
            lambda i: GraniteHybridDecoderLayer(config, config.layer_types[i]),
            multiplier=config.embedding_multiplier, routing=False)


class GraniteHybridForCausalLM(CausalLM):
    """The decoder with its head TIED to ``embed_tokens`` (one leaf: its
    gradient is the sum of the embedding's and the head's) over the held
    rows of the vocabulary."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__(config, GraniteHybridModel(config), tied=True)

    def forward(self, input_ids):
        divide = self.config.logits_scaling
        return apply(lambda a, w: (a @ w.T) / divide, self.model(input_ids),
                     self.model.embed_tokens.weight, op_name="tied_lm_head")

    def mamba_layers(self):
        return [layer.mamba for layer in self.model.layers
                if layer.layer_type == MAMBA]

    def chunk_carry(self):
        """[mamba layers] float32 on the device: the last forward's
        ``Mamba2Mixer.chunk_carry`` of each."""
        return self.stacked(self.mamba_layers(), "chunk_carry")
