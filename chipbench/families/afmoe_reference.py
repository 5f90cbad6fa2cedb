"""The plain reference for the AFMoE family (Arcee Trinity).

Written from the equations of issue 32, which follow the row
``Trinity-Large-Preview`` of the model catalog and, for what its
``config`` has no key for, the family's ``modeling_afmoe.py`` as
remembered — the configuration file lists those points under
``assumed``. ``jax.numpy`` only, float32, matmuls at
``Precision.HIGHEST``, no kernel, no sort, no cache: attention is a dense
masked softmax, and the routed experts are a loop over the held experts
with a mask. It imports nothing of the program and takes no array the
program made: every weight is made again from the seed by ``get(group)``.

One decoder block on one sequence, sandwich norms (RMSNorm: gain, no
bias)::

    a = Norm_in(x)
    q = Norm_q(split(a Wq, nq heads of d));  k = Norm_k(split(a Wk, nkv));
    v = split(a Wv, nkv)                     (Norm_q/k: gain [d] a head)
    window layers only: q, k = RoPE(q, k)    (theta, all d dims, half-split)
    s_tj = q_t . k_j / sqrt(d), visible iff j <= t and (full or t - j < W)
    o = softmax(s) v                         (query head h reads kv head h div G)
    att = (merge(o) * sigmoid(a Wg)) Wo
    h = x + Norm_post_attn(att)
    m = Norm_pre_mlp(h)
    dense:  f = (silu(m W1) * (m W3)) W2
    routed: sc = sigmoid(m Wr)               (always float32)
            C = the top_k of sc + bias       (bias: a buffer, the choice alone)
            g_e = scale * sc_e / (sum_{c in C} sc_c + 1e-20)
            f = Shared(m) + sum_{e in C, e held} g_e Expert_e(m)
    y = h + Norm_post_mlp(f)

``x0 = wte[ids] * embed_scale``; logits ``Norm_f(y_last) W_head`` (untied).

A SHARE. The reference is given the same share as the program: experts
``first .. first + held`` of the ``experts`` the router chooses among.
A chosen expert that is not held adds nothing — what the absent chips
would have added is left out here as it is there — while the gates are
normalised over all the chosen, held or not.

FOLLOWING a routing. Top-k routing is discrete: where a bfloat16 program
ranks a token's experts otherwise than this float32 arithmetic, the
token meets another set and every number downstream is another number.
So the reference can be told the set each token meets (``follow``: for
each forward it will make, [routed blocks, B, S, k] expert ids — the
program's own, read out of its compiled step): ``C`` is then the given
set, the gates still the reference's own scores of it. Its OWN choice is
computed beside and kept in ``routing`` (ids ascending): the share of
tokens where the two SETS differ is a number of its own.

Departures, each for memory only (as ``gpt_reference``): the blocks are
walked one at a time with a hand-chained ``jax.vjp`` per block, one
batch row at a time, no parameter is held between uses, and attention is
computed a kv head and a block of queries at a time (recomputed in the
backward pass), so that no S x S x heads array exists.

``precision="fp8"`` is the control of "How correct is decided": the
operands of every matmul outside the router (attention's included)
rounded to float8-e4m3, straight-through in the backward pass. It must
FAIL the comparison the program passes.

Weights by name. ``embed``: ``wte`` [V, H]. Block ``h.<i>``: ``norm_in.g``,
``wq`` [H, nq*d], ``wk``, ``wv`` [H, nkv*d], ``wg`` [H, nq*d], ``wo``
[nq*d, H], ``q_norm.g``, ``k_norm.g`` [d], ``norm_post_attn.g``,
``norm_pre_mlp.g``, ``norm_post_mlp.g``; a dense block ``w1``, ``w3``
[H, I], ``w2`` [I, H]; a routed block ``router.w`` [H, E],
``shared.w1``, ``shared.w3`` [H, F], ``shared.w2`` [F, H], and its two
stacked expert matrices as groups of their own, ``h.<i>.gu``: ``w``
[held, H, 2F] (columns gate | up), ``h.<i>.dn``: ``w`` [held, F, H];
inside a block they go by ``experts.w_gu`` and ``experts.w_dn``.
``head``: ``norm_f.g``, ``lm_head`` [H, V].

``fixed(i, n)`` gives block i's arrays that no optimizer holds, as they
stand at the n-th training forward: ``router.bias`` [E] — whoever runs a
balancing rule moves it from forward to forward; this file knows none —
and whatever weights the configuration keeps out of AdamW.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Iterable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .gpt_reference import HIGHEST, Group, _lower, _mm

_QUERY_BLOCK = 1024


class Layer(NamedTuple):
    """What a held block is: its sliding window (None: full attention,
    no positions) and whether its MLP is routed."""
    window: Optional[int]
    routed: bool


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rope(x, theta: float):
    """x [S, heads, d]: half-split rotation of all d dims by the
    position's angle."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # [S, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_attention(q, k, v, window: Optional[int], precision: str):
    """q [S, nq, d], k / v [S, nkv, d] -> [S, nq, d]: causal (and, under
    ``window``, banded) softmax attention, one kv head and one block of
    queries at a time."""
    s, nq, d = q.shape
    nkv = k.shape[1]
    group = nq // nkv
    qb = s if s <= _QUERY_BLOCK else _QUERY_BLOCK
    assert s % qb == 0, (s, qb)

    def one(at):
        j, c = at                               # kv head, block of queries
        qc = jax.lax.dynamic_slice(q, (c * qb, j * group, 0), (qb, group, d))
        kj = jax.lax.dynamic_index_in_dim(k, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(v, j, 1, keepdims=False)
        scores = jnp.einsum("qgd,kd->gqk", _lower(qc, precision),
                            _lower(kj, precision), precision=HIGHEST)
        scores = scores / math.sqrt(d)
        behind = (c * qb + jnp.arange(qb))[:, None] - jnp.arange(s)[None, :]
        visible = behind >= 0
        if window is not None:
            visible &= behind < window
        probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), -1)
        return jnp.einsum("gqk,kd->qgd", _lower(probs, precision),
                          _lower(vj, precision), precision=HIGHEST)

    js, cs = jnp.meshgrid(jnp.arange(nkv), jnp.arange(s // qb), indexing="ij")
    out = jax.lax.map(jax.checkpoint(one), (js.reshape(-1), cs.reshape(-1)))
    out = out.reshape(nkv, s // qb, qb, group, d)    # [j, c, q, g, d]
    return out.transpose(1, 2, 0, 3, 4).reshape(s, nq, d)


def attention(p: Group, a, *, nq: int, nkv: int, d: int, theta: float,
              eps: float, window: Optional[int], precision: str = "f32"):
    """Gated attention on one sequence: a [S, H] -> [S, H]."""
    s = a.shape[0]
    q = _rms_norm(_mm(a, p["wq"], precision).reshape(s, nq, d),
                  p["q_norm.g"], eps)
    k = _rms_norm(_mm(a, p["wk"], precision).reshape(s, nkv, d),
                  p["k_norm.g"], eps)
    v = _mm(a, p["wv"], precision).reshape(s, nkv, d)
    if window is not None:
        q, k = rope(q, theta), rope(k, theta)
    o = softmax_attention(q, k, v, window, precision).reshape(s, nq * d)
    gate = jax.nn.sigmoid(_mm(a, p["wg"], precision))
    return _mm(o * gate, p["wo"], precision)


def swiglu(m, w1, w3, w2, precision: str):
    return _mm(jax.nn.silu(_mm(m, w1, precision)) * _mm(m, w3, precision),
               w2, precision)


def route(p: Group, m, *, top_k: int, scale: float, forced=None):
    """The router (always float32): m [S, H] -> (the experts each token
    meets [S, k]: ``forced`` where given, else its own choice; their
    gates [S, k]; the own choice [S, k], ids ascending; scores [S, E])."""
    scores = jax.nn.sigmoid(jnp.matmul(m, p["router.w"], precision=HIGHEST))
    _, own = jax.lax.top_k(scores + p["router.bias"], top_k)
    own = jnp.sort(own.astype(jnp.int32), axis=-1)
    chosen = own if forced is None else forced.astype(jnp.int32)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    gates = scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen, gates, own, scores


def moe(p: Group, m, forced=None, *, top_k: int, scale: float, first: int,
        precision: str = "f32"):
    """The shared expert beside the routed ones, of which the experts
    ``first .. first + held`` are here: a loop over them, each run on the
    whole sequence and weighted by the gate of the tokens that chose it
    (0 elsewhere). Returns the output and (own choice, mean chosen
    score)."""
    chosen, gates, own, scores = route(p, m, top_k=top_k, scale=scale,
                                       forced=forced)
    f = p["experts.w_dn"].shape[1]

    def add_expert(out, ex):
        w_gu, w_dn, e = ex
        gu = _mm(m, w_gu, precision)
        y = _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_dn, precision)
        weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        return out + weight[:, None] * y, None

    held = p["experts.w_gu"].shape[0]
    routed, _ = jax.lax.scan(      # a loop: one body to compile
        add_expert, jnp.zeros_like(m),
        (p["experts.w_gu"], p["experts.w_dn"], first + jnp.arange(held)))
    shared = swiglu(m, p["shared.w1"], p["shared.w3"], p["shared.w2"],
                    precision)
    top = jnp.mean(jnp.take_along_axis(scores, own, axis=1), axis=-1)
    return shared + routed, (own, top)


def block(p: Group, fixed: Group, x, forced=None, *, layer: Layer, eps: float,
          top_k: int, scale: float, first: int, precision: str = "f32",
          **attn):
    """One decoder block on one sequence: x [S, H] -> ([S, H], routing or
    None). ``fixed``: the block's arrays that get no gradient."""
    p = dict(p, **fixed)
    a = _rms_norm(x, p["norm_in.g"], eps)
    att = attention(p, a, eps=eps, window=layer.window, precision=precision,
                    **attn)
    h = x + _rms_norm(att, p["norm_post_attn.g"], eps)
    m = _rms_norm(h, p["norm_pre_mlp.g"], eps)
    routing = None
    if layer.routed:
        f, routing = moe(p, m, forced, top_k=top_k, scale=scale, first=first,
                         precision=precision)
    else:
        f = swiglu(m, p["w1"], p["w3"], p["w2"], precision)
    return h + _rms_norm(f, p["norm_post_mlp.g"], eps), routing


def head(p: Group, x, *, eps: float, precision: str = "f32"):
    """x [S, H] -> logits [S, V]."""
    return _mm(_rms_norm(x, p["norm_f.g"], eps), p["lm_head"], precision)


def _row_loss(p, x, labels, *, eps, precision):
    logp = jax.nn.log_softmax(head(p, x, eps=eps, precision=precision))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


class Reference:
    """The model by groups of weights made on demand: ``get(group)`` gives
    the float32 weights of ``"embed"``, ``"h.<i>"``, ``"h.<i>.gu"``,
    ``"h.<i>.dn"`` (routed blocks) or ``"head"``, ``fixed(i, n)`` block
    i's arrays that get no gradient, at training forward n; nothing is
    kept between calls. ``layers``: what each held block is. ``follow``:
    for each forward of ``loss_and_grads``, in order, the [routed blocks,
    B, S, k] experts its tokens are to meet (module docstring); without
    it every forward routes by its own choice. ``routing`` holds, after a
    forward, each ROUTED block's (own choice [B, S, k] ascending, mean
    chosen score [B, S]) as numpy arrays; ``first_routing`` keeps that
    of the first forward made."""

    def __init__(self, get: Callable[[str], Group], fixed: Callable, *,
                 layers: Sequence[Layer], nq: int, nkv: int, d: int,
                 theta: float, eps: float, top_k: int, scale: float,
                 first_expert: int, embed_scale: float,
                 follow: Optional[Iterable] = None, precision: str = "f32"):
        self.get, self.fixed, self.layers = get, fixed, list(layers)
        self.embed_scale = embed_scale
        self._follow = None if follow is None else iter(follow)
        kw = dict(eps=eps, precision=precision)
        self._block, self._block_bwd = {}, {}
        for layer in set(self.layers):
            blk = functools.partial(
                block, layer=layer, nq=nq, nkv=nkv, d=d, theta=theta,
                top_k=top_k, scale=scale, first=first_expert, **kw)
            self._block[layer] = jax.jit(blk)
            self._block_bwd[layer] = jax.jit(self._bwd_of(blk))
        self._logits = jax.jit(functools.partial(head, **kw))
        loss = functools.partial(_row_loss, **kw)
        self._head_bwd = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        self.routing, self.first_routing = [], None
        self._forwards = 0       # training forwards made so far

    @staticmethod
    def _bwd_of(blk):
        def block_bwd(p, fixed, x, forced, dy):
            _, vjp, _ = jax.vjp(lambda p, x: blk(p, fixed, x, forced), p, x,
                                has_aux=True)
            return vjp(dy)
        return block_bwd

    def block_params(self, get, i: int) -> Group:
        """Block i's weights under the names ``block`` knows."""
        if not self.layers[i].routed:
            return get(f"h.{i}")
        return dict(get(f"h.{i}"), **{
            "experts.w_gu": get(f"h.{i}.gu")["w"],
            "experts.w_dn": get(f"h.{i}.dn")["w"]})

    def _forced(self, forced, i: int, r: int):
        """The set row r meets in block i: ``forced`` counts the routed
        blocks alone."""
        if forced is None or not self.layers[i].routed:
            return None
        return forced[sum(l.routed for l in self.layers[:i])][r]

    def _forward(self, get, ids, forced=None):
        """Activations entering each block, and after the last, per row."""
        n = self._forwards
        wte = get("embed")["wte"]
        rows = range(ids.shape[0])
        xs = [[wte[ids[r]] * self.embed_scale for r in rows]]
        del wte
        self.routing = []
        for i, layer in enumerate(self.layers):
            p, fixed = self.block_params(get, i), self.fixed(i, n)
            outs = [self._block[layer](p, fixed, xs[-1][r],
                                       self._forced(forced, i, r))
                    for r in rows]
            xs.append([o[0] for o in outs])
            if layer.routed:
                self.routing.append(tuple(
                    np.stack([np.asarray(o[1][k]) for o in outs])
                    for k in range(2)))
        if self.first_routing is None:
            self.first_routing = self.routing
        return xs

    def logits(self, ids: np.ndarray):
        """[B, S, V] of one batch (the tests' forward), routed by the
        reference's own choice."""
        ids = jnp.asarray(ids, jnp.int32)
        xs = self._forward(self.get, ids)[-1]
        p = self.get("head")
        return np.stack([np.asarray(self._logits(p, x)) for x in xs])

    def loss_and_grads(self, get, ids: np.ndarray, labels: np.ndarray):
        """Generator over one batch [B, S]: yields ``(group, grads)`` from
        ``head`` down to ``embed``, a routed block as its three groups
        (the caller consumes and drops each), and sets ``self.loss`` to
        the batch's mean loss."""
        rows = ids.shape[0]
        ids = jnp.asarray(ids, jnp.int32)
        labels = jnp.asarray(labels, jnp.int32)
        forced = None
        if self._follow is not None:
            given = next(self._follow, None)
            if given is None:
                raise ValueError("a forward more than there are routings "
                                 "to follow")
            forced = jnp.asarray(given, jnp.int32)
        xs = self._forward(get, ids, forced)
        n, self._forwards = self._forwards, self._forwards + 1
        add = functools.partial(jax.tree_util.tree_map, jnp.add)
        p = get("head")
        total, grads, dxs = 0.0, None, []
        for r in range(rows):
            loss, (g, dx) = self._head_bwd(p, xs[-1][r], labels[r])
            total += float(loss) / rows
            grads = g if grads is None else add(grads, g)
            dxs.append(dx / rows)
        self.loss = total
        yield "head", jax.tree_util.tree_map(lambda a: a / rows, grads)
        for i in reversed(range(len(self.layers))):
            layer = self.layers[i]
            p, fixed, grads = self.block_params(get, i), self.fixed(i, n), None
            for r in range(rows):
                g, dxs[r] = self._block_bwd[layer](
                    p, fixed, xs[i][r], self._forced(forced, i, r), dxs[r])
                grads = g if grads is None else add(grads, g)
            xs.pop()
            del p
            if layer.routed:
                yield f"h.{i}.dn", {"w": grads.pop("experts.w_dn")}
                yield f"h.{i}.gu", {"w": grads.pop("experts.w_gu")}
            yield f"h.{i}", grads
        wte = get("embed")["wte"]
        d_wte = jnp.zeros_like(wte)
        del wte
        for r in range(rows):
            d_wte = d_wte.at[ids[r]].add(dxs[r] * self.embed_scale)
        yield "embed", {"wte": d_wte}


def routing_stats(routing, experts: int) -> Dict[str, float]:
    """Of one forward's ``Reference.routing``: the mean chosen score and,
    over the routed blocks, the smallest and largest of (an expert's
    pairs / its even share)."""
    top = float(np.mean([np.mean(t) for _, t in routing]))
    loads = [np.bincount(e.reshape(-1), minlength=experts)
             / (e.size / experts) for e, _ in routing]
    return {"chosen_score_mean": top,
            "load_min": float(min(l.min() for l in loads)),
            "load_max": float(max(l.max() for l in loads))}
