"""SmallThinker-family decoder LM (PowerInfer SmallThinker-21BA3B /
4BA0.6B; ``model_name`` smallthinker_*) — training path.

The stack is DECLARED by the published keys: layer ``i`` attends inside
a sliding window with RoPE (``sliding_window_layout[i] == 1``,
``rope_layout[i] == 1``) or over everything before it without positions
(both 0: the first layer of each four), and every MLP is routed. What
sets the family apart is WHERE the block routes: the expert choice is
made from the block's INPUT, before attention, so that the plan of the
dispatch depends on nothing attention produces (in a deployment the
exchange is issued under attention). One block::

    x  : the block's input
    r  = x Wr                       float32, no bias; the residual stream
                                    as it enters, before any norm
    p  = softmax(r) over all E;  ids = top-k(p);  gates = p[ids] / sum p[ids]
    a  = RMSNorm_in(x)
    q, k, v = a Wq, a Wk, a Wv      nq / nkv / nkv heads of d, no bias,
                                    no q/k norm
    window layers: q, k = RoPE(q, k) (theta, all d dims, half-split)
    h  = x + merge(flash(q, k, v, causal, window)) Wo
    m  = RMSNorm_post(h)
    f  = sum_j gates_j (relu(m Wg_e) * (m Wu_e)) Wd_e,  e = ids_j    (ReGLU)
    y  = h + f                      no shared expert
    logits = RMSNorm_f(x_L) W_head  untied, no bias

``nn.SoftmaxTopKRouter`` over ``nn.RoutedExperts`` told its activation
(``"reglu"``). A model may hold a SHARE of what is published, as
``models/afmoe.py``: ``held_layers`` layers from ``first_layer`` on,
``held_experts`` experts a layer from ``first_expert`` on. ``recompute``
names what a block recomputes in its backward (``"none"``, or ``"mlp"``:
the post-attention norm and the routed experts, which hold a row for
every (token, choice) pair) through
``fleet.utils.recompute``; the router is OUTSIDE the recomputed half: its
choice is made once, first, and handed across attention as an input.

Not here: the secondary experts the family's description speaks of (the
21B model's published config has primary keys only), and the sigmoid
form of the router (``moe_primary_router_apply_softmax`` false). **Training
forward only: no cache, no engine** (ROADMAP M1 e).

The plain float32 reference of the same equations is
``chipbench/families/smallthinker_reference.py``;
``tests/test_smallthinker.py`` holds the two together.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

import jax

from .. import nn
from ..base.tape import apply
from .decoder import DecoderStack, RoutedCausalLM, held_share, rope


@dataclass
class SmallThinkerConfig:
    """The published keys by their published names
    (SmallThinker-21BA3B-Instruct's values), then what of it a model
    holds."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rope_layout: Optional[Sequence[int]] = None     # default: 0 1 1 1 ...
    sliding_window_layout: Optional[Sequence[int]] = None
    sliding_window_size: int = 4096
    rope_theta: float = 1.5e6
    rms_norm_eps: float = 1e-6
    # the share held here (default: everything)
    first_layer: int = 0
    held_layers: Optional[int] = None
    first_expert: int = 0
    held_experts: Optional[int] = None
    recompute: str = "none"

    @property
    def num_experts(self) -> int:
        return self.moe_num_primary_experts

    def __post_init__(self):
        n = self.num_hidden_layers
        for field in ("rope_layout", "sliding_window_layout"):
            if getattr(self, field) is None:
                setattr(self, field, [int(i % 4 != 0) for i in range(n)])
            if len(getattr(self, field)) != n:
                raise ValueError(f"{field} names every published layer")
        if not self.moe_primary_router_apply_softmax:
            raise NotImplementedError(
                "moe_primary_router_apply_softmax false (a sigmoid router)")
        held_share(self)
        if self.recompute not in ("none", "mlp"):
            raise ValueError(f"recompute={self.recompute!r}")

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=64, num_hidden_layers=8,
                    num_attention_heads=6, num_key_value_heads=2,
                    head_dim=16, moe_ffn_hidden_size=32,
                    moe_num_primary_experts=8,
                    moe_num_active_primary_experts=3, sliding_window_size=16)
        base.update(kw)
        return SmallThinkerConfig(**base)


class SmallThinkerAttention(nn.Layer):
    """Plain GQA attention, no q/k norm, no gate; ``window``: the sliding
    window (keys a query sees, itself included), or None: full causal
    attention; ``positions``: whether q and k are rotated. Training
    forward only."""

    def __init__(self, config: SmallThinkerConfig, window: Optional[int],
                 positions: bool):
        super().__init__()
        c = config
        self.nq, self.nkv, self.d = (c.num_attention_heads,
                                     c.num_key_value_heads, c.head_dim)
        self.window, self.positions = window, positions
        self.theta = float(c.rope_theta)
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
            if self.positions:
                q = apply(lambda t: rope(t, self.theta), q, op_name="rope")
                k = apply(lambda t: rope(t, self.theta), k, op_name="rope")
        with jax.named_scope("attn.full" if self.window is None
                             else "attn.window"):
            out = apply(
                lambda q, k, v: flash_attention(q, k, v, True, None, None,
                                                self.window),
                q, k, v, op_name="flash_attention")
        with jax.named_scope("attn.project"):
            return self.o_proj(M.reshape(out, [b, s, self.nq * self.d]))


class SmallThinkerMoE(nn.Layer):
    """The router and the ReGLU experts it chooses among. They are two
    calls, not one ``forward``: the block routes on its input and runs
    the experts after attention."""

    def __init__(self, config: SmallThinkerConfig):
        super().__init__()
        c = config
        self.router = nn.SoftmaxTopKRouter(
            c.hidden_size, c.moe_num_primary_experts,
            c.moe_num_active_primary_experts, c.norm_topk_prob)
        self.experts = nn.RoutedExperts(
            c.hidden_size, c.moe_ffn_hidden_size, c.moe_num_primary_experts,
            c.held_experts, c.first_expert, activation="reglu")


class SmallThinkerDecoderLayer(nn.Layer):
    """One block: route on the input, attend, run the chosen experts."""

    routed = True       # every MLP of the family is (RoutedCausalLM asks)

    def __init__(self, config: SmallThinkerConfig, window: Optional[int],
                 positions: bool):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.self_attn = SmallThinkerAttention(config, window, positions)
        self.mlp = SmallThinkerMoE(config)
        self.recompute = config.recompute
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)

    def _attn_half(self, x):
        return x + self.self_attn(self.input_layernorm(x))

    def _mlp_half(self, h, ids, gates):
        """-> (y, rows each held expert got): the choice comes in, made
        before attention."""
        f, sizes = self.mlp.experts.compute(
            self.post_attention_layernorm(h), ids, gates)
        return h + f, sizes

    def forward(self, x, routing=None):
        """``routing``: a list that is given the block's choice, expert
        ids [B, S, k]."""
        from ..distributed.fleet.utils.recompute import recompute

        with jax.named_scope("moe.router.ahead"):
            ids, gates = self.mlp.router(x)
        h = self._attn_half(x)
        y, sizes = (recompute(self._mlp_half, h, ids, gates)
                    if self.recompute == "mlp"
                    else self._mlp_half(h, ids, gates))
        # a buffer cannot be written under recompute: counted here
        self.mlp.experts.count(sizes, math.prod(ids.shape))
        if routing is not None:
            routing.append(ids)
        return y


class SmallThinkerModel(DecoderStack):
    def __init__(self, config: SmallThinkerConfig):
        c = config
        super().__init__(c, lambda i: SmallThinkerDecoderLayer(
            c, c.sliding_window_size if c.sliding_window_layout[i] else None,
            bool(c.rope_layout[i])))


class SmallThinkerForCausalLM(RoutedCausalLM):
    """The decoder with its untied head."""

    def __init__(self, config: SmallThinkerConfig):
        super().__init__(config, SmallThinkerModel(config))
