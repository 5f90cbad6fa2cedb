"""The plain reference for the Qwen3-Next family (``model_type``
qwen3_next).

Written from the equations of issue 34, which follow the row
``Qwen3-Next-80B-A3B-Instruct`` of the model catalog and, for what its
``config`` has no key for, the family's ``modeling_qwen3_next.py`` as
remembered — the configuration file lists those points under
``assumed``. ``jax.numpy`` only, float32, matmuls at
``Precision.HIGHEST``, no kernel, no sort, no cache. It imports nothing
of the program and takes no array the program made: every weight is made
again from the seed by ``get(group)``.

One decoder block on one sequence (``Norm(x) = x rsqrt(mean x^2 + eps)
(1 + g)``, g starting at 0)::

    a = Norm_in(x);  h = x + Mixer(a);  m = Norm_post(h);  y = h + MoE(m)

A ``linear_attention`` block mixes by a Gated DeltaNet::

    [q | k | v | z] = a W_qkvz;   [b | alpha] = a W_ba
    c_t = silu(sum_{j<4} w_j x_{t-3+j})  on the channels [q | k | v],
          zeros before the sequence (four shifted products)
    beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(alpha_t + dt_bias)
    q_t <- l2norm(q_t) / sqrt(d_k);  k_t <- l2norm(k_t)      (eps 1e-6)
    per value head (key head h // (hv / hk)), S [d_k, d_v] from zero,
    TOKEN BY TOKEN (never a chunked form):
        S <- exp(g_t) S;  u = S^T k_t;  delta = beta_t (v_t - u)
        S <- S + k_t delta^T;  o_t = S^T q_t
    out = (RMSNorm_dv(o_t) w silu(z_t)) W_o        w [d_v], a plain gain

a ``full_attention`` block by gated softmax attention::

    [q | gate] = a W_q;  k = a W_k;  v = a W_v
    q, k <- Norm(q), Norm(k) per head; RoPE (half-split) on the first
            ``rot`` dims of each head
    out = (merge(softmax(q k^T / sqrt(d), causal) v) sigmoid(gate)) W_o

and every MLP is routed::

    p = softmax(m W_r)                           (always float32)
    C = the top_k of p
    f = sigmoid(m w_s) Shared(m)
        + sum_{e in C, e held} (p_e / sum_{c in C} p_c) Expert_e(m)

``x0 = wte[ids]``; logits ``Norm_f(y_last) W_head`` (untied).

A SHARE and FOLLOWING a routing: as ``afmoe_reference`` (whose
``Reference`` this file's extends: the walk over the blocks, one
hand-chained ``jax.vjp`` a block, is the same). The recurrence is a scan
over blocks of 128 tokens, each recomputed in the backward pass, so that
a block's states and not the sequence's are held; an expert is computed
again in the backward pass for the same reason.

``precision="fp8"`` is the control of "How correct is decided": the
operands of every matmul outside the router (attention's and the
recurrence's q, k, v included) rounded to float8-e4m3.
``precision="forget"`` is this architecture's own fault: float32, with
the recurrence's state set to zero at every 64th token — what a chunked
scan that drops its carry computes. Both must FAIL the comparison the
program passes.

Weights by name. ``embed``: ``wte`` [V, H]. Block ``h.<i>``:
``norm_in.g``, ``norm_post.g`` [H]; linear: ``gdn.w_qkvz`` [H, 2 hk dk +
2 hv dv], ``gdn.w_ba`` [H, 2 hv], ``gdn.conv`` [4, 2 hk dk + hv dv] (tap 0
the oldest), ``gdn.a_log``, ``gdn.dt_bias`` [hv], ``gdn.norm.g`` [dv],
``gdn.wo`` [hv dv, H]; full: ``wq`` [H, 2 nq d], ``wk``, ``wv`` [H, nkv
d], ``wo`` [nq d, H], ``q_norm.g``, ``k_norm.g`` [d]; then ``router.w``
[H, E], ``shared.w1``, ``shared.w3`` [H, F], ``shared.w2`` [F, H],
``shared.gate`` [H, 1], and the two stacked expert matrices as groups of
their own, ``h.<i>.gu``: ``w`` [held, H, 2F], ``h.<i>.dn``: ``w`` [held,
F, H]. ``head``: ``norm_f.g``, ``lm_head`` [H, V]. Every array is trained: the walk this
file inherits hands a block its ``fixed`` arrays, of which there are none.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Iterable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from . import afmoe_reference
from .afmoe_reference import softmax_attention, swiglu
from .gpt_reference import HIGHEST, Group, _lower, _mm

LINEAR, FULL = "linear_attention", "full_attention"
FORGET_EVERY = 64      # the fault's period: the program's chunk
_SCAN_BLOCK = 128


class Layer(NamedTuple):
    """What a held block is; every MLP of the family is routed."""
    kind: str
    routed: bool = True


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + g)


def rope(x, theta: float, rot: int):
    """x [S, heads, d]: half-split rotation of the first ``rot`` dims."""
    s = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def conv(x, w):
    """x [S, C], w [taps, C]: ``c_t = sum_j w_j x_{t - (taps-1) + j}``,
    zeros before the sequence."""
    s, taps = x.shape[0], w.shape[0]
    return sum(jnp.pad(x, ((taps - 1 - j, 0), (0, 0)))[:s] * w[j]
               for j in range(taps))


def recurrence(q, k, v, g, beta, forget: bool = False):
    """The gated delta rule token by token: q, k [S, hv, dk], v [S, hv,
    dv], g, beta [S, hv] -> o [S, hv, dv]."""
    s, hv, dk = q.shape
    dv = v.shape[-1]

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t, t = x
        if forget:
            state = jnp.where(t % FORGET_EVERY == 0, 0.0, state)
        state = jnp.exp(g_t)[:, None, None] * state
        u = jnp.einsum("hkv,hk->hv", state, k_t, precision=HIGHEST)
        delta = b_t[:, None] * (v_t - u)
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t, precision=HIGHEST)

    blk = math.gcd(s, _SCAN_BLOCK)
    xs = [a.reshape(s // blk, blk, *a.shape[1:])
          for a in (q, k, v, g, beta, jnp.arange(s))]
    _, o = jax.lax.scan(
        jax.checkpoint(lambda state, x: jax.lax.scan(step, state, x)),
        jnp.zeros((hv, dk, dv), jnp.float32), xs)
    return o.reshape(s, hv, dv)


def gated_delta_net(p: Group, a, *, hk: int, hv: int, dk: int, dv: int,
                    eps: float, precision: str = "f32", forget: bool = False):
    """The linear-attention mixer on one sequence: a [S, H] -> [S, H]."""
    s = a.shape[0]
    wide = 2 * hk * dk + hv * dv
    qkvz = _mm(a, p["gdn.w_qkvz"], precision)
    ba = _mm(a, p["gdn.w_ba"], precision)
    c = jax.nn.silu(conv(qkvz[:, :wide], p["gdn.conv"]))
    q, k, v = jnp.split(c, [hk * dk, 2 * hk * dk], axis=-1)

    def unit(x):
        x = x.reshape(s, hk, dk)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        return jnp.repeat(x, hv // hk, axis=1)

    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["gdn.a_log"]) * jax.nn.softplus(
        ba[:, hv:] + p["gdn.dt_bias"])
    o = recurrence(_lower(unit(q) / math.sqrt(dk), precision),
                   _lower(unit(k), precision),
                   _lower(v.reshape(s, hv, dv), precision), g, beta, forget)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    o = o * p["gdn.norm.g"] * jax.nn.silu(qkvz[:, wide:].reshape(s, hv, dv))
    return _mm(o.reshape(s, hv * dv), p["gdn.wo"], precision)


def attention(p: Group, a, *, nq: int, nkv: int, d: int, theta: float,
              rot: int, eps: float, precision: str = "f32"):
    """Gated full attention on one sequence: a [S, H] -> [S, H]."""
    s = a.shape[0]
    qg = _mm(a, p["wq"], precision)
    q = _norm(qg[:, :nq * d].reshape(s, nq, d), p["q_norm.g"], eps)
    k = _norm(_mm(a, p["wk"], precision).reshape(s, nkv, d), p["k_norm.g"],
              eps)
    v = _mm(a, p["wv"], precision).reshape(s, nkv, d)
    o = softmax_attention(rope(q, theta, rot), rope(k, theta, rot), v, None,
                          precision).reshape(s, nq * d)
    return _mm(o * jax.nn.sigmoid(qg[:, nq * d:]), p["wo"], precision)


def route(p: Group, m, *, top_k: int, forced=None):
    """The router (always float32): m [S, H] -> (the experts each token
    meets [S, k]: ``forced`` where given, else its own choice; their
    gates [S, k]; the own choice [S, k], ids ascending; probabilities
    [S, E])."""
    probs = jax.nn.softmax(jnp.matmul(m, p["router.w"], precision=HIGHEST),
                           -1)
    _, own = jax.lax.top_k(probs, top_k)
    own = jnp.sort(own.astype(jnp.int32), axis=-1)
    chosen = own if forced is None else forced.astype(jnp.int32)
    picked = jnp.take_along_axis(probs, chosen, axis=1)
    return chosen, picked / jnp.sum(picked, -1, keepdims=True), own, probs


def moe(p: Group, m, forced=None, *, top_k: int, first: int,
        precision: str = "f32"):
    """The gated shared expert beside the routed ones, of which the
    experts ``first .. first + held`` are here: a loop over them, each run
    on the whole sequence and weighted by the gate of the tokens that
    chose it (0 elsewhere). Returns the output and (own choice, mean
    chosen probability)."""
    chosen, gates, own, probs = route(p, m, top_k=top_k, forced=forced)
    f = p["experts.w_dn"].shape[1]

    @jax.checkpoint
    def expert(w_gu, w_dn, e, m, chosen, gates):
        gu = _mm(m, w_gu, precision)
        y = _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_dn, precision)
        weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        return weight[:, None] * y

    held = p["experts.w_gu"].shape[0]
    routed, _ = jax.lax.scan(      # a loop: one body to compile
        lambda out, ex: (out + expert(*ex, m, chosen, gates), None),
        jnp.zeros_like(m),
        (p["experts.w_gu"], p["experts.w_dn"], first + jnp.arange(held)))
    shared = jax.nn.sigmoid(jnp.matmul(m, p["shared.gate"], precision=HIGHEST)
                            ) * swiglu(m, p["shared.w1"], p["shared.w3"],
                                       p["shared.w2"], precision)
    top = jnp.mean(jnp.take_along_axis(probs, own, axis=1), axis=-1)
    return shared + routed, (own, top)


def block(p: Group, fixed: Group, x, forced=None, *, layer: Layer, eps: float,
          top_k: int, first: int, gdn, attn, precision: str = "f32",
          forget: bool = False):
    """One decoder block on one sequence: x [S, H] -> ([S, H], routing).
    ``fixed``: the inherited walk's arrays that get no gradient (none
    here); ``gdn`` / ``attn``: the sizes of the two mixers."""
    a = _norm(x, p["norm_in.g"], eps)
    if layer.kind == LINEAR:
        mixed = gated_delta_net(p, a, eps=eps, precision=precision,
                                forget=forget, **gdn)
    else:
        mixed = attention(p, a, eps=eps, precision=precision, **attn)
    h = x + mixed
    f, routing = moe(p, _norm(h, p["norm_post.g"], eps), forced, top_k=top_k,
                     first=first, precision=precision)
    return h + f, routing


def head(p: Group, x, *, eps: float, precision: str = "f32"):
    """x [S, H] -> logits [S, V]."""
    return _mm(_norm(x, p["norm_f.g"], eps), p["lm_head"], precision)


def _row_loss(p, x, labels, *, eps, precision):
    logp = jax.nn.log_softmax(head(p, x, eps=eps, precision=precision))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


class Reference(afmoe_reference.Reference):
    """The model by groups of weights made on demand, as
    ``afmoe_reference.Reference`` (whose walk over the blocks, forward
    and backward, this is): ``layers`` says what each held block is,
    ``gdn`` and ``attn`` the two mixers' sizes. ``precision``: ``"f32"``,
    ``"fp8"`` or ``"forget"`` (module docstring)."""

    def __init__(self, get: Callable[[str], Group], *,
                 layers: Sequence[Layer], gdn: dict, attn: dict, eps: float,
                 top_k: int, first_expert: int,
                 follow: Optional[Iterable] = None, precision: str = "f32"):
        self.get, self.layers = get, list(layers)
        self.fixed = lambda block, step: {}
        self.embed_scale = 1.0
        self._follow = None if follow is None else iter(follow)
        forget = precision == "forget"
        kw = dict(eps=eps, precision="f32" if forget else precision)
        self._block, self._block_bwd = {}, {}
        for layer in set(self.layers):
            blk = functools.partial(
                block, layer=layer, top_k=top_k, first=first_expert, gdn=gdn,
                attn=attn, forget=forget, **kw)
            self._block[layer] = jax.jit(blk)
            self._block_bwd[layer] = jax.jit(self._bwd_of(blk))
        self._logits = jax.jit(functools.partial(head, **kw))
        loss = functools.partial(_row_loss, **kw)
        self._head_bwd = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        self.routing, self.first_routing = [], None
        self._forwards = 0       # training forwards made so far
