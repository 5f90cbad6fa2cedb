"""The plain reference for the SmallThinker family (PowerInfer
SmallThinker-21BA3B-Instruct).

Written from the equations of issue 44, which follow the row
``SmallThinker-21BA3B-Instruct`` of the model catalog and, for what its
``config`` has no key for, the family's ``modeling_smallthinker.py`` as
remembered — the configuration file lists those points under
``assumed``. ``jax.numpy`` only, float32, matmuls at
``Precision.HIGHEST``, no kernel, no sort, no cache: attention is a dense
masked softmax, and the routed experts are a loop over the held experts
with a mask. It imports nothing of the program and takes no array the
program made: every weight is made again from the seed by ``get(group)``.

One decoder block on one sequence (RMSNorm: gain, no bias)::

    x  : the block's input [S, H]
    r  = x Wr                       always float32, no bias: the router reads
                                    the residual stream AS IT ENTERS, before
                                    the input norm and before attention [A1]
    p  = softmax(r) over all E;  C = the top_k of p
    g_e = p_e / sum_{c in C} p_c    (norm_topk_prob)
    a  = Norm_in(x)
    q, k, v = split(a Wq, nq heads of d), split(a Wk, nkv), split(a Wv, nkv)
                                    no bias, no q/k norm [A2]
    positions (layout 1): q, k = RoPE(q, k)   theta, all d dims, half-split
    s_tj = q_t . k_j / sqrt(d), visible iff j <= t and (no window or t - j < W)
    o  = softmax(s) v               (query head h reads kv head h div G)
    h  = x + merge(o) Wo
    m  = Norm_post(h)
    f  = sum_{e in C, e held} g_e (relu(m Wg_e) * (m Wu_e)) Wd_e     ReGLU,
                                    no shared expert
    y  = h + f

``x0 = wte[ids]``; logits ``Norm_f(y_last) W_head`` (untied); the loss is
mean next-token cross-entropy.

A SHARE and FOLLOWING a routing: as ``afmoe_reference`` (whose
``Reference`` this file's extends: the walk over the blocks, one
hand-chained ``jax.vjp`` a block, a row at a time, attention a kv head
and a block of queries at a time, is the same, so that 16,384 tokens
fit). A chosen expert that is not held adds nothing, while the gates are
normalised over all the chosen, held or not. Told the set each token
meets (``follow``), ``C`` is the given set and the gates the reference's
own probabilities of it; its OWN choice is kept beside. An expert is
computed again in the backward pass, so that one expert's rows and not
sixteen's are held. The router's arithmetic (softmax over all, the k
largest, renormalised) is ``qwen3next_reference.route``, given the
block's input here; the head and its loss are ``afmoe_reference``'s.

``precision="fp8"`` is the control of "How correct is decided": the
operands of every matmul outside the router (attention's included)
rounded to float8-e4m3. ``precision="late"`` is this architecture's own
fault: float32, with the router reading ``m``, the normed stream AFTER
attention, where every other decoder of the repository routes. Both must
FAIL the comparison the program passes.

Weights by name. ``embed``: ``wte`` [V, H]. Block ``h.<i>``:
``norm_in.g``, ``norm_post.g`` [H], ``wq`` [H, nq d], ``wk``, ``wv`` [H,
nkv d], ``wo`` [nq d, H], ``router.w`` [H, E], and the two stacked expert
matrices as groups of their own, ``h.<i>.gu``: ``w`` [held, H, 2F]
(columns gate | up), ``h.<i>.dn``: ``w`` [held, F, H]; inside a block
they go by ``experts.w_gu`` and ``experts.w_dn``. ``head``: ``norm_f.g``,
``lm_head`` [H, V]. Every array is trained: the walk this file inherits
hands a block its ``fixed`` arrays, of which there are none.
"""
from __future__ import annotations

import functools
from typing import Callable, Iterable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from . import afmoe_reference
from .afmoe_reference import (_rms_norm, _row_loss, head, rope,
                              softmax_attention)
from .gpt_reference import Group, _mm
from .qwen3next_reference import route  # softmax over all, top-k, renormalised


class Layer(NamedTuple):
    """What a held block is: its sliding window (None: full attention)
    and whether q and k are rotated; every MLP of the family is routed."""
    window: Optional[int]
    positions: bool
    routed: bool = True


def attention(p: Group, a, *, nq: int, nkv: int, d: int, theta: float,
              layer: Layer, precision: str = "f32"):
    """Plain GQA attention on one sequence: a [S, H] -> [S, H]."""
    s = a.shape[0]
    q = _mm(a, p["wq"], precision).reshape(s, nq, d)
    k = _mm(a, p["wk"], precision).reshape(s, nkv, d)
    v = _mm(a, p["wv"], precision).reshape(s, nkv, d)
    if layer.positions:
        q, k = rope(q, theta), rope(k, theta)
    o = softmax_attention(q, k, v, layer.window, precision)
    return _mm(o.reshape(s, nq * d), p["wo"], precision)


def experts(p: Group, m, chosen, gates, *, first: int,
            precision: str = "f32"):
    """The ReGLU experts ``first .. first + held`` on m [S, H], given the
    choice: a loop over them, each run on the whole sequence and weighted
    by the gate of the tokens that chose it (0 elsewhere)."""
    f = p["experts.w_dn"].shape[1]

    @jax.checkpoint
    def expert(w_gu, w_dn, e, m, chosen, gates):
        gu = _mm(m, w_gu, precision)
        y = _mm(jax.nn.relu(gu[:, :f]) * gu[:, f:], w_dn, precision)
        weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        return weight[:, None] * y

    held = p["experts.w_gu"].shape[0]
    out, _ = jax.lax.scan(         # a loop: one body to compile
        lambda out, ex: (out + expert(*ex, m, chosen, gates), None),
        jnp.zeros_like(m),
        (p["experts.w_gu"], p["experts.w_dn"], first + jnp.arange(held)))
    return out


def block(p: Group, fixed: Group, x, forced=None, *, layer: Layer, eps: float,
          top_k: int, first: int, precision: str = "f32", late: bool = False,
          **attn):
    """One decoder block on one sequence: x [S, H] -> ([S, H], (own
    choice, mean chosen probability)). ``fixed``: the inherited walk's
    arrays that get no gradient (none here); ``late``: the fault, the
    router reading the normed stream after attention."""
    if not late:
        chosen, gates, own, probs = route(p, x, top_k=top_k, forced=forced)
    h = x + attention(p, _rms_norm(x, p["norm_in.g"], eps), layer=layer,
                      precision=precision, **attn)
    m = _rms_norm(h, p["norm_post.g"], eps)
    if late:
        chosen, gates, own, probs = route(p, m, top_k=top_k, forced=forced)
    f = experts(p, m, chosen, gates, first=first, precision=precision)
    top = jnp.mean(jnp.take_along_axis(probs, own, axis=1), axis=-1)
    return h + f, (own, top)


class Reference(afmoe_reference.Reference):
    """The model by groups of weights made on demand, as
    ``afmoe_reference.Reference`` (whose walk over the blocks, forward
    and backward, this is): ``layers`` says what each held block is.
    ``precision``: ``"f32"``, ``"fp8"`` or ``"late"`` (module
    docstring)."""

    def __init__(self, get: Callable[[str], Group], *,
                 layers: Sequence[Layer], nq: int, nkv: int, d: int,
                 theta: float, eps: float, top_k: int, first_expert: int,
                 follow: Optional[Iterable] = None, precision: str = "f32"):
        self.get, self.layers = get, list(layers)
        self.fixed = lambda block, step: {}
        self.embed_scale = 1.0
        self._follow = None if follow is None else iter(follow)
        late = precision == "late"
        kw = dict(eps=eps, precision="f32" if late else precision)
        self._block, self._block_bwd = {}, {}
        for layer in set(self.layers):
            blk = functools.partial(
                block, layer=layer, nq=nq, nkv=nkv, d=d, theta=theta,
                top_k=top_k, first=first_expert, late=late, **kw)
            self._block[layer] = jax.jit(blk)
            self._block_bwd[layer] = jax.jit(self._bwd_of(blk))
        self._logits = jax.jit(functools.partial(head, **kw))
        loss = functools.partial(_row_loss, **kw)
        self._head_bwd = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        self.routing, self.first_routing = [], None
        self._forwards = 0       # training forwards made so far
