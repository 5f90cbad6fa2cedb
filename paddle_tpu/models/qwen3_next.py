"""Qwen3-Next-family decoder LM (``model_type`` qwen3_next) — training
path.

The stack is DECLARED by the published keys: layer ``i`` mixes its tokens
by full softmax attention iff ``(i + 1) % full_attention_interval == 0``
(``layer_types[i] == "full_attention"``) and by a Gated DeltaNet
recurrence otherwise (``"linear_attention"``); every MLP is routed
(``mlp_only_layers`` empty, ``decoder_sparse_step`` 1). One block::

    h = x + Mixer(Norm(x));   y = h + MoE(Norm(h))
    Norm(x) = x rsqrt(mean x^2 + eps) (1 + g)        float32, g starts at 0

Gated DeltaNet (``GatedDeltaNet``; a = the normed stream)::

    [q | k | v | z] = a W_qkvz;   [b | alpha] = a W_ba
    [q | k | v] <- silu(causal depthwise conv, 4 taps, no bias)
    q <- l2norm(q) / sqrt(d_k);   k <- l2norm(k)     per head, eps 1e-6
            both lines are ONE op, ops/gdn_inputs.py::conv_silu_l2norm:
            float32 inside, and that float32 lives in VMEM alone
    beta = sigmoid(b);   g = -exp(A_log) softplus(alpha + dt_bias)
    o = gated_delta_rule(q, k, v, g, beta)           ops/gated_delta_rule.py
    out = (RMSNorm_dv(o) w silu(z)) W_o              w [d_v], starts at 1
            the gate is ONE op, ops/gated_norm.py::gated_rms_norm, float32
            inside its kernels (``gdn_gate`` is its plain form)

Full attention (``Qwen3NextAttention``)::

    [q | gate] = a W_q;  k = a W_k;  v = a W_v
    q, k <- Norm(q), Norm(k) per head;  RoPE on the first
            partial_rotary_factor of each head (half-split)
    out = (flash(q, k, v, causal) sigmoid(gate)) W_o

MoE (``Qwen3NextMoE``): ``nn.SoftmaxTopKRouter`` (softmax over all the
experts, then the k largest, renormalised) over ``nn.RoutedExperts``,
beside a shared SwiGLU expert behind a sigmoid gate of its own,
``sigmoid(m w_s) Shared(m)``.

A model may hold a SHARE of what is published, as ``models/afmoe.py``:
``held_layers`` layers from ``first_layer`` on, ``held_experts`` experts
a layer from ``first_expert`` on. ``recompute`` names what a block
computes again in its backward (``"none"``, ``"mlp"``: its MoE half,
``"layer"``: all of it) through ``fleet.utils.recompute``.

Not here: the multi-token-prediction head and the auxiliary balancing
loss. The published projections
interleave their outputs by key-head group; here they are laid out
``[q | k | v | z]``, ``[b | alpha]``, ``[q | gate]``, heads contiguous
inside each part: a permutation of the columns of a matrix. **Training
forward only: no cache, no engine** (ROADMAP M4).

The plain float32 reference of the same equations is
``chipbench/families/qwen3next_reference.py``;
``tests/test_qwen3_next.py`` holds the two together.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..base.tape import apply
from ..nn import functional as F
from ..nn import initializer as I
from .decoder import (DecoderStack, RoutedCausalLM, SwiGLU, held_share,
                      partial_rope)

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass
class Qwen3NextConfig:
    """The published keys by their published names
    (Qwen3-Next-80B-A3B-Instruct's values), then what of it a model
    holds."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    layer_types: Optional[Sequence[str]] = None
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rms_norm_eps: float = 1e-6
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    # the share held here (default: everything)
    first_layer: int = 0
    held_layers: Optional[int] = None
    first_expert: int = 0
    held_experts: Optional[int] = None
    recompute: str = "none"

    def __post_init__(self):
        if self.layer_types is None:
            n = self.full_attention_interval
            self.layer_types = [FULL if (i + 1) % n == 0 else LINEAR
                                for i in range(self.num_hidden_layers)]
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every published layer")
        held_share(self)
        if self.recompute not in ("none", "mlp", "layer"):
            raise ValueError(f"recompute={self.recompute!r}")

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=64, num_hidden_layers=8,
                    linear_num_key_heads=2, linear_num_value_heads=4,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, num_experts=8, num_experts_per_tok=3,
                    moe_intermediate_size=32,
                    shared_expert_intermediate_size=32)
        base.update(kw)
        return Qwen3NextConfig(**base)


def zero_centred_rms_norm(x, g, eps: float):
    """``x rsqrt(mean x^2 + eps) (1 + g)`` over the last axis, in
    float32."""
    f = x.astype(jnp.float32)
    f = f * jax.lax.rsqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + eps)
    return (f * (1.0 + g.astype(jnp.float32))).astype(x.dtype)


class Qwen3NextRMSNorm(nn.Layer):
    """RMSNorm whose gain is ``1 + weight``, ``weight`` starting at 0."""

    def __init__(self, size: int, eps: float):
        super().__init__()
        self.eps = eps
        self.weight = self.create_parameter(
            [size], default_initializer=I.Constant(0.0))

    def forward(self, x):
        return apply(
            jax.checkpoint(functools.partial(zero_centred_rms_norm,
                                             eps=self.eps)),
            x, self.weight, op_name="zero_centred_rms_norm")


def gdn_inputs(qkv, ba, w, a_log, dt_bias, *, hk: int, hv: int, dk: int,
               dv: int):
    """The projections to what the recurrence takes. ``qkv`` [B, S, C]
    passes a causal depthwise convolution (``w`` [taps, C], tap 0 the
    oldest position, zeros before the sequence) and SiLU; then q, k [B,
    S, hk, dk] are normalised (q also scaled by 1 / sqrt(dk)) and
    returned with v [B, S, hv, dv] in ``qkv``'s type: one pass over
    ``qkv`` forward and one backward, every step of it float32 inside the
    kernels of ``ops/gdn_inputs.py``, which keep ``qkv`` and ``w`` alone
    for the backward pass. g and beta [B, S, hv] are float32."""
    from ..ops.gdn_inputs import conv_silu_l2norm

    q, k, v = conv_silu_l2norm(qkv, w, hk, hv, dk, dv)
    ba = ba.astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        ba[..., hv:] + dt_bias.astype(jnp.float32))
    return q, k, v, g, beta


def gdn_gate(o, z, w, eps: float):
    """``RMSNorm(o) w silu(z)`` per head [.., dv] (float32), heads
    merged: the plain form of ``ops/gated_norm.py``, which the layer
    runs; the tests hold the kernels to it."""
    f = o.astype(jnp.float32)
    f = f * jax.lax.rsqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + eps)
    f = f * w.astype(jnp.float32) * jax.nn.silu(
        z.astype(jnp.float32).reshape(o.shape))
    return f.astype(o.dtype).reshape(*o.shape[:2], -1)


class GatedDeltaNet(nn.Layer):
    """The linear-attention mixer (module docstring). Training forward
    only: the convolution's and the recurrence's state are kept nowhere."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        c = config
        self.hk, self.hv = c.linear_num_key_heads, c.linear_num_value_heads
        self.dk, self.dv = c.linear_key_head_dim, c.linear_value_head_dim
        self.eps = c.rms_norm_eps
        key, value = self.hk * self.dk, self.hv * self.dv
        self.conv_dim = 2 * key + value
        self.in_proj_qkvz = nn.Linear(c.hidden_size, self.conv_dim + value,
                                      bias_attr=False)
        self.in_proj_ba = nn.Linear(c.hidden_size, 2 * self.hv,
                                    bias_attr=False)
        self.conv1d_weight = self.create_parameter(
            [c.linear_conv_kernel_dim, self.conv_dim],
            default_initializer=I.Normal(0.0, 0.02))
        # the published start draws A uniformly from (0, 16); here an
        # even ramp over it, so that a layer is built without a seed
        self.A_log = self.create_parameter(
            [self.hv], default_initializer=I.Assign(
                jnp.log(jnp.linspace(0.5, 16.0, self.hv))))
        self.dt_bias = self.create_parameter(
            [self.hv], default_initializer=I.Constant(1.0))
        self.norm_weight = self.create_parameter(
            [self.dv], default_initializer=I.Constant(1.0))
        self.out_proj = nn.Linear(value, c.hidden_size, bias_attr=False)

    def forward(self, a):
        from ..ops.gated_delta_rule import gated_delta_rule
        from ..ops.gated_norm import gated_rms_norm

        with jax.named_scope("gdn.project"):
            qkvz, ba = self.in_proj_qkvz(a), self.in_proj_ba(a)
        # the [S, 8192] part is two kernels whose custom VJP keeps its
        # inputs alone; g and beta keep their [S, 32] float32 insides
        with jax.named_scope("gdn.conv"):
            q, k, v, g, beta = apply(
                functools.partial(gdn_inputs, hk=self.hk, hv=self.hv,
                                  dk=self.dk, dv=self.dv),
                qkvz[:, :, :self.conv_dim], ba, self.conv1d_weight,
                self.A_log, self.dt_bias, op_name="gdn_inputs")
        with jax.named_scope("gdn.scan"):
            o = apply(gated_delta_rule, q, k, v, g, beta,
                      op_name="gated_delta_rule")
        # two kernels whose custom VJP keeps o, z and the gain alone
        with jax.named_scope("gdn.gate"):
            out = apply(
                lambda o, z, w: gated_rms_norm(
                    o.reshape(z.shape), z, w, self.eps),
                o, qkvz[:, :, self.conv_dim:], self.norm_weight,
                op_name="gdn_gate")
            return self.out_proj(out)


class Qwen3NextAttention(nn.Layer):
    """Gated full causal attention with zero-centred q/k norms and RoPE
    on a part of each head. Training forward only."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        c = config
        self.nq, self.nkv, self.d = (c.num_attention_heads,
                                     c.num_key_value_heads, c.head_dim)
        self.theta = float(c.rope_theta)
        self.rot = int(c.head_dim * c.partial_rotary_factor)
        h, q, k = c.hidden_size, self.nq * self.d, self.nkv * self.d
        self.q_proj = nn.Linear(h, 2 * q, bias_attr=False)     # [q | gate]
        self.k_proj = nn.Linear(h, k, bias_attr=False)
        self.v_proj = nn.Linear(h, k, bias_attr=False)
        self.o_proj = nn.Linear(q, h, bias_attr=False)
        self.q_norm = Qwen3NextRMSNorm(self.d, c.rms_norm_eps)
        self.k_norm = Qwen3NextRMSNorm(self.d, c.rms_norm_eps)

    def forward(self, a):
        from ..ops.flash_attention import flash_attention
        from ..tensor import manipulation as M

        b, s = a.shape[0], a.shape[1]
        width = self.nq * self.d

        def turned(t):
            return apply(lambda t: partial_rope(
                t, self.theta, self.rot).astype(t.dtype), t, op_name="rope")

        with jax.named_scope("attn.project"):
            qg = self.q_proj(a)
            q = turned(self.q_norm(M.reshape(qg[:, :, :width],
                                             [b, s, self.nq, self.d])))
            k = turned(self.k_norm(M.reshape(self.k_proj(a),
                                             [b, s, self.nkv, self.d])))
            v = M.reshape(self.v_proj(a), [b, s, self.nkv, self.d])
        with jax.named_scope("attn.full"):
            out = apply(lambda q, k, v: flash_attention(q, k, v, True),
                        q, k, v, op_name="flash_attention")
        with jax.named_scope("attn.gate"):
            out = M.reshape(out, [b, s, width])
            return self.o_proj(out * F.sigmoid(qg[:, :, width:]))


class Qwen3NextMoE(nn.Layer):
    """The gated shared expert beside the routed ones."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        c = config
        self.router = nn.SoftmaxTopKRouter(
            c.hidden_size, c.num_experts, c.num_experts_per_tok,
            c.norm_topk_prob)
        self.shared_expert = SwiGLU(c.hidden_size,
                                    c.shared_expert_intermediate_size)
        self.shared_expert_gate = nn.Linear(c.hidden_size, 1, bias_attr=False)
        self.experts = nn.RoutedExperts(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.held_experts, c.first_expert)

    def compute(self, m):
        """-> (f, ids [.., k], rows each held expert got): the counters
        are the caller's to move (``experts.count``)."""
        with jax.named_scope("moe.router"):
            ids, gates = self.router(m)
        with jax.named_scope("moe.shared"):
            shared = F.sigmoid(self.shared_expert_gate(m)) \
                * self.shared_expert(m)
        routed, sizes = self.experts.compute(m, ids, gates)
        return shared + routed, ids, sizes


class Qwen3NextDecoderLayer(nn.Layer):
    """One block around the mixer its ``layer_type`` names."""

    routed = True       # every MLP of the family is (RoutedCausalLM asks)

    def __init__(self, config: Qwen3NextConfig, layer_type: str):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.layer_type, self.recompute = layer_type, config.recompute
        if layer_type == LINEAR:
            self.linear_attn = GatedDeltaNet(config)
        else:
            self.self_attn = Qwen3NextAttention(config)
        self.mlp = Qwen3NextMoE(config)
        self.input_layernorm = Qwen3NextRMSNorm(h, eps)
        self.post_attention_layernorm = Qwen3NextRMSNorm(h, eps)

    def _mixer_half(self, x):
        mixer = (self.linear_attn if self.layer_type == LINEAR
                 else self.self_attn)
        return x + mixer(self.input_layernorm(x))

    def _mlp_half(self, h):
        """-> (y, ids, rows each held expert got)."""
        f, ids, sizes = self.mlp.compute(self.post_attention_layernorm(h))
        return h + f, ids, sizes

    def _whole(self, x):
        return self._mlp_half(self._mixer_half(x))

    def forward(self, x, routing=None):
        """``routing``: a list that is given the block's choice, expert
        ids [B, S, k]."""
        from ..distributed.fleet.utils.recompute import recompute

        if self.recompute == "layer":
            y, ids, sizes = recompute(self._whole, x)
        elif self.recompute == "mlp":
            y, ids, sizes = recompute(self._mlp_half, self._mixer_half(x))
        else:
            y, ids, sizes = self._whole(x)
        # a buffer cannot be written under recompute: counted here
        self.mlp.experts.count(sizes, math.prod(ids.shape))
        if routing is not None:
            routing.append(ids)
        return y


class Qwen3NextModel(DecoderStack):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__(
            config,
            lambda i: Qwen3NextDecoderLayer(config, config.layer_types[i]),
            norm=Qwen3NextRMSNorm)


class Qwen3NextForCausalLM(RoutedCausalLM):
    """The decoder with its untied head."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__(config, Qwen3NextModel(config))
