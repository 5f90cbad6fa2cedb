"""AFMoE-family decoder LM (Arcee Trinity; ``model_type`` afmoe) —
training path.

The stack is DECLARED by the published keys: layer ``i`` attends over a
sliding window (``layer_types[i] == "sliding_attention"``, with RoPE) or
over everything before it (``"full_attention"``, no positions at all),
and its MLP is dense (``i < num_dense_layers``) or routed. One attention
class takes ``window: Optional[int]``, one block class takes its MLP::

    x0 = embed[ids] * sqrt(hidden)                       (mup_enabled)
    a  = RMSNorm_in(x)
    q, k = RMSNorm_q(a Wq), RMSNorm_k(a Wk) per head;  v = a Wv
    window layers: q, k = RoPE(q, k)
    att = (flash(q, k, v, causal, window) * sigmoid(a Wg)) Wo
    h  = x + RMSNorm_post_attn(att)                      (sandwich norms)
    f  = MLP(RMSNorm_pre_mlp(h));   y = h + RMSNorm_post_mlp(f)

Dense MLP: SwiGLU of width ``intermediate_size``. Routed MLP: an ungated
shared expert beside ``nn.SigmoidTopKRouter`` (sigmoid scores, the k best
of score + bias, gates renormalised and scaled) over ``nn.RoutedExperts``.
A model may hold a SHARE of what is published — ``held_layers`` layers
from ``first_layer`` on, ``held_experts`` experts a layer from
``first_expert`` on: one chip's part of a pipelined, expert-parallel job
— and computes that part alone. ``recompute`` names what a block
recomputes in its backward (``"none"``, or ``"mlp"``: its MLP half,
whose routed form holds the large activations — a row for every
(token, choice) pair) through ``fleet.utils.recompute``.

Not here: the published bias controller (nothing moves the router's
``bias``) and the depth-scaled initialisation of the sandwich norms'
gains (they start at 1). **Training forward only: no cache, no engine**
(ROADMAP M1 e, M3).

The plain float32 reference of the same equations is
``chipbench/families/afmoe_reference.py``; ``tests/test_afmoe.py`` holds
the two together.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

import jax

from .. import nn
from ..base.tape import apply
from ..nn import functional as F
from .decoder import DecoderStack, RoutedCausalLM, SwiGLU, held_share, rope

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class AfmoeConfig:
    """The published keys by their published names (Trinity-Large-Preview's
    values), then what of it a model holds. ``route_norm`` and
    ``mup_enabled`` are two of those keys and are passed through from a
    configuration's file; only their published side (true) is run by any
    cell."""
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Optional[Sequence[str]] = None   # default: every 4th full
    global_attn_every_n_layers: int = 4
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    mup_enabled: bool = True
    # the share held here (default: everything)
    first_layer: int = 0
    held_layers: Optional[int] = None
    first_expert: int = 0
    held_experts: Optional[int] = None
    recompute: str = "none"

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = [FULL if i % n == n - 1 else SLIDING
                                for i in range(self.num_hidden_layers)]
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every published layer")
        held_share(self)
        if self.recompute not in ("none", "mlp"):
            raise ValueError(f"recompute={self.recompute!r}")

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_hidden_layers=8,
                    num_dense_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, sliding_window=16,
                    num_experts=8, num_experts_per_tok=2)
        base.update(kw)
        return AfmoeConfig(**base)


class AfmoeAttention(nn.Layer):
    """Gated GQA attention with q/k norms; ``window``: the sliding
    window (keys a query sees, itself included; RoPE), or None: full
    causal attention without positions. Training forward only."""

    def __init__(self, config: AfmoeConfig, window: Optional[int]):
        super().__init__()
        c = config
        self.nq, self.nkv, self.d = (c.num_attention_heads,
                                     c.num_key_value_heads, c.head_dim)
        self.window, self.theta = window, float(c.rope_theta)
        h, q, k = c.hidden_size, self.nq * self.d, self.nkv * self.d
        self.q_proj = nn.Linear(h, q, bias_attr=False)
        self.k_proj = nn.Linear(h, k, bias_attr=False)
        self.v_proj = nn.Linear(h, k, bias_attr=False)
        self.gate_proj = nn.Linear(h, q, bias_attr=False)
        self.o_proj = nn.Linear(q, h, bias_attr=False)
        self.q_norm = nn.RMSNorm(self.d, c.rms_norm_eps)
        self.k_norm = nn.RMSNorm(self.d, c.rms_norm_eps)

    def forward(self, a):
        from ..ops.flash_attention import flash_attention
        from ..tensor import manipulation as M

        b, s = a.shape[0], a.shape[1]
        with jax.named_scope("attn.project"):
            q = self.q_norm(M.reshape(self.q_proj(a), [b, s, self.nq, self.d]))
            k = self.k_norm(M.reshape(self.k_proj(a), [b, s, self.nkv, self.d]))
            v = M.reshape(self.v_proj(a), [b, s, self.nkv, self.d])
            if self.window is not None:
                q = apply(lambda t: rope(t, self.theta), q, op_name="rope")
                k = apply(lambda t: rope(t, self.theta), k, op_name="rope")
        with jax.named_scope("attn.full" if self.window is None
                             else "attn.window"):
            out = apply(
                lambda q, k, v: flash_attention(q, k, v, True, None, None,
                                                self.window),
                q, k, v, op_name="flash_attention")
        with jax.named_scope("attn.gate"):
            out = M.reshape(out, [b, s, self.nq * self.d])
            return self.o_proj(out * F.sigmoid(self.gate_proj(a)))


class AfmoeMoE(nn.Layer):
    """The shared expert (ungated) beside the routed ones."""

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        c = config
        self.router = nn.SigmoidTopKRouter(
            c.hidden_size, c.num_experts, c.num_experts_per_tok,
            c.route_scale, c.route_norm)
        self.shared_experts = SwiGLU(
            c.hidden_size, c.moe_intermediate_size * c.num_shared_experts)
        self.experts = nn.RoutedExperts(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.held_experts, c.first_expert)

    def compute(self, m):
        """-> (f, ids [.., k], rows each held expert got): the counters
        are the caller's to move (``experts.count``)."""
        with jax.named_scope("moe.router"):
            ids, gates = self.router(m)
        with jax.named_scope("moe.shared"):
            shared = self.shared_experts(m)
        routed, sizes = self.experts.compute(m, ids, gates)
        return shared + routed, ids, sizes


class AfmoeDecoderLayer(nn.Layer):
    """One block around the attention and the MLP it is given."""

    def __init__(self, config: AfmoeConfig, self_attn: AfmoeAttention, mlp):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.self_attn, self.mlp = self_attn, mlp
        self.routed = isinstance(mlp, AfmoeMoE)
        self.recompute = config.recompute
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)
        self.pre_mlp_layernorm = nn.RMSNorm(h, eps)
        self.post_mlp_layernorm = nn.RMSNorm(h, eps)

    def _attn_half(self, x):
        return x + self.post_attention_layernorm(
            self.self_attn(self.input_layernorm(x)))

    def _mlp_half(self, h):
        """-> (y,) or, routed, (y, ids, rows each held expert got)."""
        m = self.pre_mlp_layernorm(h)
        if not self.routed:
            with jax.named_scope("mlp.dense"):
                return (h + self.post_mlp_layernorm(self.mlp(m)),)
        f, ids, sizes = self.mlp.compute(m)
        return h + self.post_mlp_layernorm(f), ids, sizes

    def forward(self, x, routing=None):
        """``routing``: a list that is given a routed block's choice,
        expert ids [B, S, k]."""
        from ..distributed.fleet.utils.recompute import recompute

        h = self._attn_half(x)
        out = (recompute(self._mlp_half, h) if self.recompute == "mlp"
               else self._mlp_half(h))
        if self.routed:
            # a buffer cannot be written under recompute: counted here
            _, ids, sizes = out
            self.mlp.experts.count(sizes, math.prod(ids.shape))
            if routing is not None:
                routing.append(ids)
        return out[0]


class AfmoeModel(DecoderStack):
    def __init__(self, config: AfmoeConfig):
        c = config

        def block(i):
            return AfmoeDecoderLayer(
                c,
                AfmoeAttention(c, c.sliding_window
                               if c.layer_types[i] == SLIDING else None),
                SwiGLU(c.hidden_size, c.intermediate_size)
                if i < c.num_dense_layers else AfmoeMoE(c))

        super().__init__(c, block, multiplier=(
            math.sqrt(c.hidden_size) if c.mup_enabled else None))


class AfmoeForCausalLM(RoutedCausalLM):
    """The decoder with its untied head."""

    def __init__(self, config: AfmoeConfig):
        super().__init__(config, AfmoeModel(config))
