"""The plain reference for the Granite-4.0-H family (``model_type``
granitemoehybrid with no routed experts).

Written from the equations of issue 40, which follow the row
``granite-4.0-h-micro`` of the model catalog and, for what its ``config``
has no key for, the builder's memory of ``modeling_granitemoehybrid.py``
and of the Mamba-2 paper (Dao & Gu, arXiv:2405.21060) — the configuration
file lists those points under ``assumed``. ``jax.numpy`` only, float32,
matmuls at ``Precision.HIGHEST``, no kernel, no cache. It imports nothing
of the program and takes no array the program made: every weight is made
again from the seed by ``get(group)``.

One decoder block on one sequence (``Norm(x) = x rsqrt(mean x^2 + eps)
g``; ``r = residual_multiplier``)::

    a = Norm_in(x);  h = x + r Mixer(a);  m = Norm_post(h)
    y = h + r (silu(m W1) * (m W3)) W2

A ``mamba`` block mixes by a Mamba-2 state-space layer (H heads of P, ONE
group of state size N: every head reads the same B and C)::

    [z | xBC | dt] = a W_in
    xBC = silu(conv(xBC) + b_conv)        causal, depthwise: taps shifted products
    [x | B | C] = xBC;   dt = softplus(dt + dt_bias);   A = -exp(A_log)
    per head h, S [P, N] from zero, TOKEN BY TOKEN (never a chunked form):
        S <- exp(dt_t A_h) S + dt_t x_t B_t^T;   y_t = S C_t + D_h x_t
    out = Norm_g(merge(y) * silu(z)) W_out      the gate BEFORE the norm

an ``attention`` block by grouped-query causal softmax attention with NO
position term::

    q = a W_q (nq heads), k, v = a W_k, a W_v (nkv heads)
    out = merge(softmax_causal(q k^T * attention_multiplier) v) W_o

``x0 = embedding_multiplier wte[ids]``; logits ``(Norm_f(y_last) wte^T) /
logits_scaling``: the head is TIED, and ``wte``'s gradient is the sum of
the embedding's and the head's.

``precision="fp8"`` is the control of "How correct is decided": the
operands of every matmul (attention's q, k, v and the recurrence's x, B,
C included) rounded to float8-e4m3. This architecture's own fault,
float32: ``"forget"`` sets the recurrence's state to zero at every 256th
token (a chunked scan that drops its carry at the published chunk). Both
must FAIL the comparison the program passes.

Weights by name. ``embed``: ``wte`` [V, H]. Block ``h.<i>``:
``norm_in.g``, ``norm_post.g`` [H]; ``w1``, ``w3`` [H, I], ``w2`` [I, H];
mamba: ``ssm.w_in`` [H, 2 H P + 2 N + heads], ``ssm.conv.w`` [taps, H P +
2 N] (tap 0 the oldest), ``ssm.conv.b`` [H P + 2 N], ``ssm.dt_bias``,
``ssm.a_log``, ``ssm.d`` [heads], ``ssm.norm.g`` [H P], ``ssm.w_out`` [H
P, H]; attention: ``wq`` [H, nq d], ``wk``, ``wv`` [H, nkv d], ``wo`` [nq
d, H]. ``head``: ``norm_f.g`` [H].
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .afmoe_reference import swiglu
from .gpt_reference import HIGHEST, Group, _lower, _mm

MAMBA, ATTENTION = "mamba", "attention"
FORGET_EVERY = 256     # the "forget" fault's period: the published chunk
_SCAN_BLOCK = 128
_QUERY_BLOCK = 256
_MLP_ROWS = 4096       # tokens of the SwiGLU computed at a time


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def conv(x, w):
    """The causal depthwise convolution as shifted products: x [S, C], w
    [taps, C] (tap 0 the oldest position; zeros before the sequence)."""
    taps, s = w.shape[0], x.shape[0]
    return sum(jnp.pad(x, ((taps - 1 - j, 0), (0, 0)))[:s] * w[j]
               for j in range(taps))


def recurrence(x, dt, a, b, c, d, forget: bool = False):
    """The state-space recurrence token by token: x [S, heads, P], dt [S,
    heads], a, d [heads], b, c [S, N] -> y [S, heads, P]."""
    s, heads, p = x.shape

    def step(state, inp):
        x_t, dt_t, b_t, c_t, t = inp
        if forget:
            state = jnp.where(t % FORGET_EVERY == 0, 0.0, state)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HIGHEST)

    blk = math.gcd(s, _SCAN_BLOCK)
    xs = [v.reshape(s // blk, blk, *v.shape[1:])
          for v in (x, dt, b, c, jnp.arange(s))]
    _, y = jax.lax.scan(
        jax.checkpoint(lambda state, inp: jax.lax.scan(step, state, inp)),
        jnp.zeros((heads, p, b.shape[-1]), jnp.float32), xs)
    return y.reshape(s, heads, p) + d[:, None] * x


def mamba(p: Group, a, *, heads: int, d: int, n: int, eps: float,
          precision: str = "f32", forget: bool = False):
    """The state-space mixer on one sequence: a [S, H] -> [S, H]."""
    s, inner = a.shape[0], heads * d
    z, xbc, dt = jnp.split(_mm(a, p["ssm.w_in"], precision),
                           [inner, 2 * inner + 2 * n], axis=-1)
    xbc = jax.nn.silu(conv(xbc, p["ssm.conv.w"]) + p["ssm.conv.b"])
    x, b, c = jnp.split(_lower(xbc, precision), [inner, inner + n], axis=-1)
    y = recurrence(x.reshape(s, heads, d),
                   jax.nn.softplus(dt + p["ssm.dt_bias"]),
                   -jnp.exp(p["ssm.a_log"]), b, c, p["ssm.d"], forget)
    gated = _norm(y.reshape(s, inner) * jax.nn.silu(z), p["ssm.norm.g"], eps)
    return _mm(gated, p["ssm.w_out"], precision)


def attention(p: Group, a, *, nq: int, nkv: int, d: int, scale: float,
              precision: str = "f32"):
    """The attention mixer on one sequence, a masked softmax one block of
    queries at a time: a [S, H] -> [S, H]. No position term."""
    s = a.shape[0]
    q = _lower(_mm(a, p["wq"], precision), precision).reshape(
        s, nkv, nq // nkv, d)
    k = _lower(_mm(a, p["wk"], precision), precision).reshape(s, nkv, d)
    v = _lower(_mm(a, p["wv"], precision), precision).reshape(s, nkv, d)
    qb = math.gcd(s, _QUERY_BLOCK)

    def one(start):
        t = start + jnp.arange(qb)
        qc = jax.lax.dynamic_slice_in_dim(q, start, qb)
        scores = jnp.einsum("tghd,kgd->gthk", qc, k,
                            precision=HIGHEST) * scale
        seen = (jnp.arange(s)[None, :] <= t[:, None])[None, :, None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gthk,kgd->tghd", _lower(probs, precision), v,
                          precision=HIGHEST)

    o = jax.lax.map(jax.checkpoint(one), qb * jnp.arange(s // qb))
    return _mm(o.reshape(s, nq * d), p["wo"], precision)


def block(p: Group, x, *, kind: str, eps: float, scale: float, ssm: dict,
          attn: dict, precision: str = "f32", forget: bool = False):
    """One decoder block on one sequence: x [S, H] -> [S, H]. ``ssm`` /
    ``attn``: the sizes of the two mixers."""
    a = _norm(x, p["norm_in.g"], eps)
    if kind == MAMBA:
        mixed = mamba(p, a, eps=eps, precision=precision, forget=forget,
                      **ssm)
    else:
        mixed = attention(p, a, precision=precision, **attn)
    h = x + scale * mixed
    m = _norm(h, p["norm_post.g"], eps)
    rows = math.gcd(m.shape[0], _MLP_ROWS)
    f = jax.lax.map(       # [S, I] float32 is 0.5 GB at the cell's size
        jax.checkpoint(lambda mc: swiglu(mc, p["w1"], p["w3"], p["w2"],
                                         precision)),
        m.reshape(-1, rows, m.shape[1])).reshape(m.shape)
    return h + scale * f


def head(p: Group, wte, x, *, eps: float, divide: float,
         precision: str = "f32"):
    """x [S, H] -> logits [S, V], through the embedding's own matrix."""
    return _mm(_norm(x, p["norm_f.g"], eps), wte.T, precision) / divide


def _row_loss(p, wte, x, labels, **kw):
    logp = jax.nn.log_softmax(head(p, wte, x, **kw))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


class Reference:
    """The model by groups of weights made on demand: ``get(group)``
    returns the float32 weights of ``"embed"``, ``"h.<i>"`` or ``"head"``;
    nothing is kept between calls. ``kinds`` says what each held block is,
    ``ssm`` and ``attn`` the two mixers' sizes. ``precision``: ``"f32"``,
    ``"fp8"`` or ``"forget"`` (module docstring)."""

    def __init__(self, get: Callable[[str], Group], *, kinds: Sequence[str],
                 ssm: dict, attn: dict, eps: float, scale: float,
                 embed_scale: float, divide: float, precision: str = "f32"):
        self.get, self.kinds = get, list(kinds)
        self.embed_scale = embed_scale
        forget = precision == "forget"
        kw = dict(eps=eps, precision="f32" if forget else precision)
        self._block, self._block_bwd = {}, {}
        for kind in set(self.kinds):
            blk = functools.partial(block, kind=kind, scale=scale, ssm=ssm,
                                    attn=attn, forget=forget, **kw)
            self._block[kind] = jax.jit(blk)
            self._block_bwd[kind] = jax.jit(self._bwd_of(blk))
        self._logits = jax.jit(functools.partial(head, divide=divide, **kw))
        loss = functools.partial(_row_loss, divide=divide, **kw)
        self._head_bwd = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    @staticmethod
    def _bwd_of(blk):
        def block_bwd(p, x, dy):
            _, vjp = jax.vjp(blk, p, x)
            return vjp(dy)
        return block_bwd

    def _forward(self, get, ids):
        """Activations entering each block, and after the last, per row."""
        wte = get("embed")["wte"]
        xs = [[wte[ids[r]] * self.embed_scale for r in range(ids.shape[0])]]
        del wte
        for i, kind in enumerate(self.kinds):
            p = get(f"h.{i}")
            xs.append([self._block[kind](p, x) for x in xs[-1]])
        return xs

    def logits(self, ids: np.ndarray):
        """[B, S, V] logits of a batch of sequences."""
        ids = jnp.asarray(ids, jnp.int32)
        last = self._forward(self.get, ids)[-1]
        p, wte = self.get("head"), self.get("embed")["wte"]
        return jnp.stack([self._logits(p, wte, x) for x in last])

    def loss_and_grads(self, get, ids: np.ndarray, labels: np.ndarray):
        """Generator over one batch [B, S]: yields ``(group, grads)`` from
        ``head`` down to ``embed`` (the caller consumes and drops each),
        and sets ``self.loss`` to the batch's mean loss. ``wte``'s
        gradient is the head's and the embedding's together."""
        rows = ids.shape[0]
        ids = jnp.asarray(ids, jnp.int32)
        labels = jnp.asarray(labels, jnp.int32)
        xs = self._forward(get, ids)
        add = functools.partial(jax.tree_util.tree_map, jnp.add)
        p, wte = get("head"), get("embed")["wte"]
        total, grads, tied, dxs = 0.0, None, None, []
        for r in range(rows):
            loss, (g, g_wte, dx) = self._head_bwd(p, wte, xs[-1][r],
                                                  labels[r])
            total += float(loss) / rows
            grads = g if grads is None else add(grads, g)
            tied = g_wte if tied is None else tied + g_wte
            dxs.append(dx / rows)
        del wte
        self.loss = total
        yield "head", jax.tree_util.tree_map(lambda a: a / rows, grads)
        for i in reversed(range(len(self.kinds))):
            p, grads = get(f"h.{i}"), None
            for r in range(rows):
                g, dxs[r] = self._block_bwd[self.kinds[i]](p, xs[i][r],
                                                           dxs[r])
                grads = g if grads is None else add(grads, g)
            xs.pop()
            yield f"h.{i}", grads
        tied = tied / rows
        for r in range(rows):
            tied = tied.at[ids[r]].add(self.embed_scale * dxs[r])
        yield "embed", {"wte": tied}
