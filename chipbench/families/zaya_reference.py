"""The plain reference for the ZAYA1 family (Zyphra).

Written from the equations of issue 26, which follow the row
``ZAYA1-8B`` of the model catalog and, for what its ``config`` has no key
for, arXiv:2510.04476 (compressed convolutional attention, CCA) and
arXiv:2511.17127 (ZAYA1) as remembered — the configuration file lists
those points under ``assumed``. ``jax.numpy`` only, float32, matmuls at
``Precision.HIGHEST``, no kernel, no sort, no cache: attention is a dense
S x S softmax, and the routed experts are a loop over all E experts with
a mask. It imports nothing of the program and takes no array the program
made: every weight is made again from the seed by ``get(group)``.

One decoder block on one sequence, pre-norm::

    h = x + CCA(RMSNorm1(x));   y = h + MoE(RMSNorm2(h))

CCA(u): ``q~ = u Wq`` [S, nq*d], ``k~ = u Wk`` [S, nkv*d]; values with a
shift, ``v_t = [u_t Wv1 ; u_{t-1} Wv2]`` (kv head 0 reads the current
token, kv head 1 the one before; ``u_{-1} = 0``); ``c = [q~ ; k~]`` passes
two causal convolutions over the sequence, ``c' = Conv1(Conv0(c))``:
Conv0 depthwise (k0 taps and a bias per channel), Conv1 grouped by head
(the d channels of a head mix among themselves, k1 taps, bias), left
zero-padded; the q-k mean ``m_q[h] = (q~[h] + k~[h // G]) / 2``,
``m_k[j] = (mean of q~ over the G heads of group j + k~[j]) / 2`` is
added: ``q = c'_q + m_q``, ``k = c'_k + m_k``; per head
``q^ = sqrt(d) q / |q|``, ``k^ = tau_j sqrt(d) k / |k|``; RoPE (half-split
rotation, theta) on the first ``rot`` dims of a head; causal softmax of
``q^ k^T / sqrt(d)`` over v, each kv head serving G query heads; ``Wo``.

MoE(w): the router in float32 whatever the precision asked for,
``r = w Wd + bd``, ``s = W3 gelu(W2 gelu(W1 r + b1) + b2) + b3``,
``p = softmax(s)``, ``e* = argmax(p + beta)``, ``g = p[e*]``; expert e is
``(silu(w Wg_e) * (w Wu_e)) Wdn_e``; ``MoE(w) = g FFN_e*(w)``. Every
token is computed (no capacity). ``beta`` is a buffer: it shifts the
choice, not the gate value, and has no gradient.

FOLLOWING a routing. Top-1 routing is discrete: where a bfloat16 program
puts a token's two best experts in the other order than this float32
arithmetic, the token meets another expert and every number downstream
is another number, whatever the precision of the rest. So the reference
can be told which expert each token meets (``follow``: for each forward
it will make, [blocks, B, S] expert ids — the program's own, read out of
its compiled step): ``e*`` is then the given one, ``g = p[e*]`` still the
reference's own probability of it, and what is left to compare is
arithmetic. Its OWN choice (the argmax above) is computed beside and
kept in ``routing``: the share of tokens where the two differ is a number
of its own.

Logits: ``RMSNormf(x_L) wte^T`` — the head is tied to the embedding, so
``wte``'s gradient is the sum of both uses.

Departures, each for memory only (as ``gpt_reference``): the blocks are
walked one at a time with a hand-chained ``jax.vjp`` per block, one
batch row at a time, and no parameter is held between uses.

``precision="fp8"`` is the control of "How correct is decided": the
operands of every matmul outside the router (attention's and the
grouped convolution's included) rounded to float8-e4m3, straight-through
in the backward pass. It must FAIL the comparison the program passes.

Weights by name. ``embed``: ``wte`` [V, H]. Block ``h.<i>``: ``norm1.g``,
``wq`` [H, nq*d], ``wk`` [H, nkv*d], ``wv1``, ``wv2`` [H, nkv*d/2],
``conv0.w`` [k0, C] (tap 0 is the oldest position), ``conv0.b`` [C],
``conv1.w`` [k1, nq+nkv, d, d] (in, out), ``conv1.b`` [C], ``tau`` [nkv],
``wo`` [nq*d, H], ``norm2.g``, ``router.wd`` [H, R], ``router.bd``,
``router.w1``, ``.b1``, ``.w2``, ``.b2`` [R, R], ``router.w3`` [R, E],
``.b3``. The two stacked expert matrices are groups of their own, so that
the optimizer's float32 copies of one group stay small: ``h.<i>.gu``:
``w`` [E, H, 2F] (columns gate | up), ``h.<i>.dn``: ``w`` [E, F, H]; inside
a block they go by ``experts.w_gu`` and ``experts.w_dn``. ``head``:
``norm_f.g``.

``fixed(i, n)`` gives block i's arrays that no optimizer holds, as they
stand at the n-th training forward (n = 0 for the first, and for
``logits``), by the names above: ``beta`` [E] — whoever runs a balancing
rule moves it from forward to forward; this file knows none — and
whatever weights the configuration keeps out of AdamW (they enter the
block like the rest and get no gradient).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .gpt_reference import HIGHEST, Group, _lower, _mm


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _shift(x, by: int):
    """``x`` [S, ...] moved ``by`` positions later, zeros coming in."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:by]), x[:-by]], axis=0)


def conv0(p: Group, c):
    """Depthwise causal convolution over the sequence: c [S, C]."""
    taps = p["conv0.w"].shape[0]
    return sum(_shift(c, taps - 1 - j) * p["conv0.w"][j]
               for j in range(taps)) + p["conv0.b"]


def conv1(p: Group, c, precision: str):
    """Causal convolution grouped by head: the d channels of a head mix
    among themselves."""
    w = p["conv1.w"]                      # [k1, heads, d_in, d_out]
    taps, heads, d, _ = w.shape
    s = c.shape[0]
    ch = c.reshape(s, heads, d)
    out = sum(jnp.einsum("sgi,gio->sgo",
                         _lower(_shift(ch, taps - 1 - j), precision),
                         _lower(w[j], precision), precision=HIGHEST)
              for j in range(taps))
    return out.reshape(s, heads * d) + p["conv1.b"]


def rope(x, theta: float, rot: int):
    """x [S, heads, d]: half-split rotation of the first ``rot`` dims by
    the position's angle; the rest passes."""
    s = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv     # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def qk_mean(qt, kt, group: int):
    """qt [S, nq, d], kt [S, nkv, d] -> (m_q [S, nq, d], m_k [S, nkv, d])."""
    s, nq, d = qt.shape
    m_q = 0.5 * (qt + jnp.repeat(kt, group, axis=1))
    m_k = 0.5 * (qt.reshape(s, nq // group, group, d).mean(axis=2) + kt)
    return m_q, m_k


def cca(p: Group, u, *, nq: int, nkv: int, d: int, theta: float, rot: int,
        precision: str = "f32"):
    """Compressed convolutional attention on one sequence: u [S, H]."""
    s = u.shape[0]
    group = nq // nkv
    qt = _mm(u, p["wq"], precision)
    kt = _mm(u, p["wk"], precision)
    v = jnp.concatenate([_mm(u, p["wv1"], precision),
                         _mm(_shift(u, 1), p["wv2"], precision)], axis=-1)
    c = conv1(p, conv0(p, jnp.concatenate([qt, kt], axis=-1)), precision)
    m_q, m_k = qk_mean(qt.reshape(s, nq, d), kt.reshape(s, nkv, d), group)
    q = c[:, :nq * d].reshape(s, nq, d) + m_q
    k = c[:, nq * d:].reshape(s, nkv, d) + m_k
    q = math.sqrt(d) * q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = (p["tau"][:, None] * math.sqrt(d) * k
         / jnp.linalg.norm(k, axis=-1, keepdims=True))
    q, k = rope(q, theta, rot), rope(k, theta, rot)
    k = jnp.repeat(k, group, axis=1)
    vh = jnp.repeat(v.reshape(s, nkv, d), group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", _lower(q, precision),
                        _lower(k, precision), precision=HIGHEST)
    scores = scores / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", _lower(probs, precision),
                   _lower(vh, precision), precision=HIGHEST)
    return _mm(o.reshape(s, nq * d), p["wo"], precision)


def route(p: Group, w, forced=None):
    """The router (always float32): w [S, H] -> (expert [S] int32 each
    token meets: ``forced`` where given, else the router's own choice;
    its gate value [S]; the own choice [S]; probabilities [S, E])."""
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    gelu = functools.partial(jax.nn.gelu, approximate=False)
    r = mm(w, p["router.wd"]) + p["router.bd"]
    a = gelu(mm(r, p["router.w1"]) + p["router.b1"])
    a = gelu(mm(a, p["router.w2"]) + p["router.b2"])
    probs = jax.nn.softmax(mm(a, p["router.w3"]) + p["router.b3"], axis=-1)
    own = jnp.argmax(probs + p["beta"], axis=-1).astype(jnp.int32)
    expert = own if forced is None else forced.astype(jnp.int32)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    return expert, gate, own, probs


def moe(p: Group, w, forced=None, precision: str = "f32"):
    """Top-1 routed gated experts, every token computed: a loop over the
    experts, each run on the whole sequence and kept where it was chosen.
    Returns the output and (the router's own choice, its top
    probability)."""
    expert, gate, own, probs = route(p, w, forced)
    f = p["experts.w_dn"].shape[1]

    def add_expert(out, ex):
        w_gu, w_dn, e = ex
        gu = _mm(w, w_gu, precision)
        y = _mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_dn, precision)
        return out + jnp.where((expert == e)[:, None], y, 0.0), None

    experts = p["experts.w_gu"].shape[0]
    out, _ = jax.lax.scan(      # a loop: one body to compile, not E
        add_expert, jnp.zeros_like(w),
        (p["experts.w_gu"], p["experts.w_dn"], jnp.arange(experts)))
    return gate[:, None] * out, (own, jnp.max(probs, axis=-1))


def block(p: Group, fixed: Group, x, forced=None, *, eps: float,
          precision: str = "f32", **attn):
    """One decoder block on one sequence: x [S, H] -> ([S, H], routing).
    ``fixed``: the block's arrays that get no gradient."""
    p = dict(p, **fixed)
    h = x + cca(p, _rms_norm(x, p["norm1.g"], eps), precision=precision,
                **attn)
    y, routing = moe(p, _rms_norm(h, p["norm2.g"], eps), forced, precision)
    return h + y, routing


def head(p: Group, wte, x, *, eps: float, precision: str = "f32"):
    """x [S, H] -> logits [S, V] through the tied embedding."""
    return _mm(_rms_norm(x, p["norm_f.g"], eps), wte.T, precision)


def _row_loss(p, wte, x, labels, *, eps, precision):
    logp = jax.nn.log_softmax(head(p, wte, x, eps=eps, precision=precision))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


class Reference:
    """The model by groups of weights made on demand: ``get(group)`` gives
    the float32 weights of ``"embed"``, ``"h.<i>"``, ``"h.<i>.gu"``,
    ``"h.<i>.dn"`` or ``"head"``, ``fixed(i, n)`` block i's arrays that
    get no gradient, at training forward n; nothing is kept between calls. ``follow``: for each
    forward of ``loss_and_grads``, in order, the [blocks, B, S] experts
    its tokens are to meet (module docstring); without it every forward
    routes by its own choice. ``routing`` holds, after a forward, each
    block's (own choice [B, S], top probability [B, S]) as numpy arrays;
    ``first_routing`` keeps that of the first forward made (step one's,
    on the seeded weights)."""

    def __init__(self, get: Callable[[str], Group], fixed: Callable, *,
                 layers: int, nq: int, nkv: int, d: int, theta: float,
                 rot: int, eps: float, follow: Optional[Iterable] = None,
                 precision: str = "f32"):
        self.get, self.fixed, self.layers = get, fixed, layers
        self._follow = None if follow is None else iter(follow)
        kw = dict(eps=eps, precision=precision)
        blk = functools.partial(block, nq=nq, nkv=nkv, d=d, theta=theta,
                                rot=rot, **kw)
        self._block = jax.jit(blk)
        self._logits = jax.jit(functools.partial(head, **kw))
        loss = functools.partial(_row_loss, **kw)
        self._head_bwd = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

        def block_bwd(p, fixed, x, forced, dy):
            _, vjp, _ = jax.vjp(lambda p, x: blk(p, fixed, x, forced), p, x,
                                has_aux=True)
            return vjp(dy)

        self._block_bwd = jax.jit(block_bwd)
        self.routing, self.first_routing = [], None
        self._forwards = 0       # training forwards made so far

    @staticmethod
    def block_params(get, i: int) -> Group:
        """Block i's weights under the names ``block`` knows."""
        return dict(get(f"h.{i}"), **{
            "experts.w_gu": get(f"h.{i}.gu")["w"],
            "experts.w_dn": get(f"h.{i}.dn")["w"]})

    def _forward(self, get, ids, forced=None):
        """Activations entering each block, and after the last, per row.
        ``forced`` [blocks, B, S]: the experts to meet."""
        n = self._forwards
        wte = get("embed")["wte"]
        rows = range(ids.shape[0])
        xs = [[wte[ids[r]] for r in rows]]
        del wte
        self.routing = []
        for i in range(self.layers):
            p, fixed = self.block_params(get, i), self.fixed(i, n)
            outs = [self._block(p, fixed, xs[-1][r],
                                None if forced is None else forced[i][r])
                    for r in rows]
            xs.append([o[0] for o in outs])
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
        p, wte = self.get("head"), self.get("embed")["wte"]
        return np.stack([np.asarray(self._logits(p, wte, x)) for x in xs])

    def loss_and_grads(self, get, ids: np.ndarray, labels: np.ndarray):
        """Generator over one batch [B, S]: yields ``(group, grads)`` from
        ``head`` down to ``embed``, a block as its three groups (the
        caller consumes and drops each),
        and sets ``self.loss`` to the batch's mean loss. ``embed``'s
        gradient is the lookup's plus the tied head's."""
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
        p, wte = get("head"), get("embed")["wte"]
        total, grads, d_wte, dxs = 0.0, None, None, []
        for r in range(rows):
            loss, (g, gw, dx) = self._head_bwd(p, wte, xs[-1][r], labels[r])
            total += float(loss) / rows
            grads = g if grads is None else add(grads, g)
            d_wte = gw if d_wte is None else d_wte + gw
            dxs.append(dx / rows)
        del wte
        self.loss = total
        yield "head", jax.tree_util.tree_map(lambda a: a / rows, grads)
        for i in reversed(range(self.layers)):
            p, fixed, grads = self.block_params(get, i), self.fixed(i, n), None
            for r in range(rows):
                g, dxs[r] = self._block_bwd(
                    p, fixed, xs[i][r],
                    None if forced is None else forced[i][r], dxs[r])
                grads = g if grads is None else add(grads, g)
            xs.pop()
            del p
            yield f"h.{i}.dn", {"w": grads.pop("experts.w_dn")}
            yield f"h.{i}.gu", {"w": grads.pop("experts.w_gu")}
            yield f"h.{i}", grads
        d_wte = d_wte / rows
        for r in range(rows):
            d_wte = d_wte.at[ids[r]].add(dxs[r])
        yield "embed", {"wte": d_wte}


def routing_stats(routing, experts: int) -> Dict[str, float]:
    """Of one forward's ``Reference.routing``: the mean top-1 probability
    and, over blocks, the smallest and largest of (an expert's tokens /
    its even share)."""
    top = float(np.mean([np.mean(t) for _, t in routing]))
    loads = [np.bincount(e.reshape(-1), minlength=experts)
             / (e.size / experts) for e, _ in routing]
    return {"top1_prob_mean": top,
            "load_min": float(min(l.min() for l in loads)),
            "load_max": float(max(l.max() for l in loads))}
