"""ZAYA1-family decoder LM (Zyphra; ``model_type`` zaya) — training path.

A pre-norm decoder block of two unusual halves::

    h = x + CCA(RMSNorm(x));   y = h + MoE(RMSNorm(h))

CCA — compressed convolutional attention (arXiv:2510.04476): queries,
keys and values live in a compressed latent (hidden 2048 -> 8 query
heads and 2 key/value heads of 128); values are ``[u_t Wv1 ; u_{t-1}
Wv2]`` (the second kv head reads the token before); ``[q~ ; k~]`` passes
two causal convolutions over the sequence (depthwise, then grouped by
head), the q-k mean is added, each head is L2-normalised (keys times a
learned temperature per kv head), RoPE turns the first half of a head's
dims, and the softmax part is ``ops.flash_attention`` (GQA, causal) and
nothing else.

MoE — ``nn.MLPRouter`` (float32, top-1, a selection bias that does not
enter the gate value) over ``nn.RoutedExperts`` (stacked SwiGLU experts,
dropless, through ``ops.grouped_matmul``).

The head is tied to ``embed_tokens`` the way ``models/llama.py::_head``
ties. What the published family describes and its ``config.json`` has no
key for (the router's averaging over depth, learned residual scaling, a
skip expert) is not here, and nothing moves the selection bias ``beta``
(a buffer; no balancing rule is run). **Training forward only: no cache
and no engine in this PR** (ROADMAP M1: serving a routed model).

The plain float32 reference of the same equations is
``chipbench/families/zaya_reference.py``; ``tests/test_zaya.py`` holds
the two together.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..base.tape import apply
from ..nn import initializer as I
from .decoder import CausalLM, DecoderStack, partial_rope


@dataclass
class ZayaConfig:
    """The published keys by their published names (ZAYA1-8B's values)."""
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16, num_experts=4, moe_intermediate_size=32,
                    router_hidden_size=16)
        base.update(kw)
        return ZayaConfig(**base)


def _shift(x, by: int):
    """x [B, S, ...] moved ``by`` positions later, zeros coming in."""
    if by == 0:
        return x
    pad = [(0, 0), (by, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def cca_conv(c, w0, b0, w1, b1):
    """``Conv1(Conv0(c))`` over the sequence, both causal by left
    zero-padding: c [B, S, C]; w0 [k0, C] depthwise (tap 0 the oldest
    position), w1 [k1, heads, d, d] grouped by head (in, out). Float32
    throughout (1,280 channels: a hundredth of the block's work); the
    grouped taps are matmuls at jax's default precision, which on a TPU
    is one bfloat16 pass with float32 accumulation."""
    f32 = jnp.float32
    c = sum(_shift(c.astype(f32), w0.shape[0] - 1 - j) * w0[j].astype(f32)
            for j in range(w0.shape[0])) + b0.astype(f32)
    taps, heads, d, _ = w1.shape
    b, s, _ = c.shape
    ch = c.reshape(b, s, heads, d)
    out = sum(jnp.einsum("bsgi,gio->bsgo", _shift(ch, taps - 1 - j),
                         w1[j].astype(f32))
              for j in range(taps))
    return out.reshape(b, s, heads * d) + b1.astype(f32)


def cca_qk(qt, kt, conv, tau, *, nq: int, nkv: int, d: int, theta: float,
           rot: int):
    """The q-k mean, the per-head L2 norm with the keys' temperature, and
    RoPE, in float32: qt [B, S, nq*d], kt [B, S, nkv*d], conv [B, S,
    (nq+nkv)*d] -> (q [B, S, nq, d], k [B, S, nkv, d]) in qt's type."""
    b, s, _ = qt.shape
    g, f32 = nq // nkv, jnp.float32
    q0 = qt.astype(f32).reshape(b, s, nq, d)
    k0 = kt.astype(f32).reshape(b, s, nkv, d)
    conv = conv.astype(f32)
    q = (conv[..., :nq * d].reshape(b, s, nq, d)
         + 0.5 * (q0 + jnp.repeat(k0, g, axis=2)))
    k = (conv[..., nq * d:].reshape(b, s, nkv, d)
         + 0.5 * (q0.reshape(b, s, nkv, g, d).mean(axis=3) + k0))
    q = q * (math.sqrt(d) * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True)))
    k = k * (math.sqrt(d) * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True))
             * tau.astype(f32)[:, None])
    return (partial_rope(q, theta, rot).astype(qt.dtype),
            partial_rope(k, theta, rot).astype(qt.dtype))


class ZayaAttention(nn.Layer):
    """Compressed convolutional attention (training forward only)."""

    def __init__(self, config: ZayaConfig):
        super().__init__()
        c = config
        self.nq, self.nkv, self.d = (c.num_attention_heads,
                                     c.num_key_value_heads, c.head_dim)
        self.theta = float(c.rope_theta)
        self.rot = int(c.head_dim * c.partial_rotary_factor)
        h, q, k = c.hidden_size, self.nq * self.d, self.nkv * self.d
        self.q_proj = nn.Linear(h, q, bias_attr=False)
        self.k_proj = nn.Linear(h, k, bias_attr=False)
        self.v1_proj = nn.Linear(h, k // 2, bias_attr=False)
        self.v2_proj = nn.Linear(h, k // 2, bias_attr=False)
        self.o_proj = nn.Linear(q, h, bias_attr=False)
        heads = self.nq + self.nkv
        init = I.Normal(0.0, 0.02)
        self.conv0_weight = self.create_parameter(
            [c.cca_time0, q + k], default_initializer=init)
        self.conv0_bias = self.create_parameter([q + k], is_bias=True)
        self.conv1_weight = self.create_parameter(
            [c.cca_time1, heads, self.d, self.d], default_initializer=init)
        self.conv1_bias = self.create_parameter([q + k], is_bias=True)
        self.temperature = self.create_parameter(
            [self.nkv], default_initializer=I.Constant(1.0))

    def forward(self, u):
        from ..ops.flash_attention import flash_attention
        from ..tensor import manipulation as M

        b, s = u.shape[0], u.shape[1]
        with jax.named_scope("cca.project"):
            qt, kt = self.q_proj(u), self.k_proj(u)
            before = apply(lambda a: _shift(a, 1), u, op_name="shift")
            v = M.concat([self.v1_proj(u), self.v2_proj(before)], axis=-1)
            v = M.reshape(v, [b, s, self.nkv, self.d])
        with jax.named_scope("cca.conv"):
            conv = apply(cca_conv, M.concat([qt, kt], axis=-1),
                         self.conv0_weight, self.conv0_bias,
                         self.conv1_weight, self.conv1_bias,
                         op_name="cca_conv")
            q, k = apply(
                lambda *a: cca_qk(*a, nq=self.nq, nkv=self.nkv, d=self.d,
                                  theta=self.theta, rot=self.rot),
                qt, kt, conv, self.temperature, op_name="cca_qk")
        with jax.named_scope("cca.attention"):
            out = apply(lambda q, k, v: flash_attention(q, k, v, True),
                        q, k, v, op_name="flash_attention")
            out = M.reshape(out, [b, s, self.nq * self.d])
            return self.o_proj(out)


class ZayaDecoderLayer(nn.Layer):
    def __init__(self, config: ZayaConfig):
        super().__init__()
        c = config
        if c.num_experts_per_tok != 1:
            raise NotImplementedError(
                "ZayaDecoderLayer routes top-1 (nn.MLPRouter); "
                f"num_experts_per_tok={c.num_experts_per_tok}")
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = ZayaAttention(c)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.router = nn.MLPRouter(c.hidden_size, c.router_hidden_size,
                                   c.num_experts)
        self.experts = nn.RoutedExperts(c.hidden_size,
                                        c.moe_intermediate_size,
                                        c.num_experts)

    def forward(self, x, routing=None):
        """``routing``: a list that is given this block's choice, expert
        ids [B, S, 1]."""
        x = x + self.self_attn(self.input_layernorm(x))
        w = self.post_attention_layernorm(x)
        with jax.named_scope("moe.router"):
            ids, gates = self.router(w)
        if routing is not None:
            routing.append(ids)
        return x + self.experts(w, ids, gates)


class ZayaModel(DecoderStack):
    def __init__(self, config: ZayaConfig):
        super().__init__(config, lambda i: ZayaDecoderLayer(config))


class ZayaForCausalLM(CausalLM):
    """The decoder with its tied head."""

    def __init__(self, config: ZayaConfig):
        if not config.tie_word_embeddings:
            raise NotImplementedError("ZAYA1 ties its head to embed_tokens")
        super().__init__(config, ZayaModel(config), tied=True)

    def forward(self, input_ids, routing=None):
        """``routing``: a list that is given every block's choice, expert
        ids [B, S, 1], in order (a train step may return them)."""
        return self._head(self.model(input_ids, routing))

    def _head(self, h):
        w = self.model.embed_tokens.weight
        return apply(lambda a, ww: a @ ww.T, h, w, op_name="tied_lm_head")

    def tokens_per_expert(self):
        """[blocks, E] int32 on the device: rows each expert of each block
        has been given since the model was built."""
        return self.stacked([layer.experts for layer in self.model.layers],
                            "tokens_per_expert")
