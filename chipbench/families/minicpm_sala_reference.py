"""The plain reference for the MiniCPM-SALA family (``model_type``
minicpm_sala).

Written from the equations of issue 38, which follow the row
``MiniCPM-SALA`` of the model catalog and, for what its ``config`` has no
key for, the family's public descriptions as remembered (the MiniCPM4
report arXiv:2506.07900 and its ``sparse_config``, InfLLM-V2
arXiv:2509.24663, Lightning Attention-2 arXiv:2401.04658) — the
configuration file lists those points under ``assumed``. ``jax.numpy``
only, float32, matmuls at ``Precision.HIGHEST``, no kernel, no cache. It
imports nothing of the program and takes no array the program made:
every weight is made again from the seed by ``get(group)``.

One decoder block on one sequence (``Norm(x) = x rsqrt(mean x^2 + eps)
g``; ``c = scale_depth / sqrt(num_hidden_layers)``)::

    a = Norm_in(x);  h = x + c Mixer(a);  m = Norm_post(h)
    y = h + c (silu(m W1) * (m W3)) W2

A ``lightning-attn`` block mixes by a decay-only linear attention::

    q, k, v = a W_q, a W_k, a W_v                (heads of d)
    q, k <- RoPE(Norm_d(q) g_q), RoPE(Norm_d(k) g_k)   half-split, whole head
    per head h, S [d, d] from zero, TOKEN BY TOKEN (never a chunked form):
        S <- lam_h S + k_t v_t^T;   o_t = S^T q_t / sqrt(d)
        lam_h = exp(-2^(-8 (h + 1) / heads))
    out = (Norm_H(merge(o)) g_o * sigmoid(a W_g)) W_o

a ``minicpm4`` block by softmax attention over a token's SET of blocks::

    q = a W_q (nq heads), k, v = a W_k, a W_v (nkv heads); Norm_d on q, k
    the set (``select``): Kc_j = mean of keys 16 j .. 16 j + 31;
        a[t, h, :] = softmax_j(q . Kc_j / sqrt(d)) over kernels ending <= t;
        A = the sum over a kv group's heads; a block's score = the max of A
        over the kernels that overlap it; block 0 and the 32 blocks up to
        t's own forced; the 64 best, ties to the lower id
    out = (merge(softmax over the keys i <= t in the set's blocks) *
           sigmoid(a W_g)) W_o

``x0 = scale_emb wte[ids]``; logits ``(Norm_f(y_last) / (H /
dim_model_base)) W_head`` (untied).

FOLLOWING a routing: a token's set is discrete exactly as a routed
token's expert set is, so (``afmoe_reference``, whose walk over the
blocks this file's ``Reference`` inherits) ``follow`` gives, for each
training forward, the sets the program's tokens read, [sparse blocks x
kv groups, B, S, topk] (ids >= the number of blocks are padding); the
attention is then a masked softmax in query blocks with the mask built
from THOSE sets, and the reference's OWN selection is computed beside it
and returned as the block's routing.

``precision="fp8"`` is the control of "How correct is decided": the
operands of every matmul (attention's and the recurrence's q, k, v
included) rounded to float8-e4m3. This architecture's own two faults,
both float32: ``"forget"`` sets the recurrence's state to zero at every
64th token (a chunked scan that drops its carry), ``"local"`` replaces
every followed set by its forced blocks alone (a kernel that ignores its
table). All three must FAIL the comparison the program passes.

Weights by name. ``embed``: ``wte`` [V, H]. Block ``h.<i>``:
``norm_in.g``, ``norm_post.g`` [H]; the SwiGLU's matrices as groups of
their own, ``h.<i>.w1``, ``h.<i>.w3``: ``w`` [H, I], ``h.<i>.w2``: ``w``
[I, H] (67M numbers each: the optimizer that follows the two steps holds
one group's parameters, moments and gradients at a time);
lightning: ``lin.wq``, ``lin.wk``, ``lin.wv``, ``lin.wg`` [H, heads d],
``lin.wo`` [heads d, H], ``lin.q_norm.g``, ``lin.k_norm.g`` [d],
``lin.o_norm.g`` [heads d]; sparse: ``wq``, ``wg`` [H, nq d], ``wk``,
``wv`` [H, nkv d], ``wo`` [nq d, H], ``q_norm.g``, ``k_norm.g`` [d].
``head``: ``norm_f.g``, ``lm_head`` [H, V].
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Iterable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import afmoe_reference
from .afmoe_reference import swiglu
from .gpt_reference import HIGHEST, Group, _lower, _mm
from .qwen3next_reference import rope

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
FORGET_EVERY = 64      # the "forget" fault's period
_SCAN_BLOCK = 128
_QUERY_BLOCK = 256
_MLP_ROWS = 4096       # tokens of the SwiGLU computed at a time
MLP = ("w1", "w3", "w2")


class Layer(NamedTuple):
    """What a held block is. ``routed`` is the inherited walk's word for
    a block with stacked expert matrices: there are none."""
    kind: str
    routed: bool = False

    @property
    def sparse(self) -> bool:
        return self.kind == SPARSE


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def slopes(heads: int):
    return 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)


def recurrence(q, k, v, forget: bool = False):
    """The decay-only linear attention token by token: q, k, v [S, heads,
    d] -> o [S, heads, d]."""
    s, heads, d = q.shape
    lam = jnp.exp(-slopes(heads))[:, None, None]

    def step(state, x):
        q_t, k_t, v_t, t = x
        if forget:
            state = jnp.where(t % FORGET_EVERY == 0, 0.0, state)
        state = lam * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision=HIGHEST) / math.sqrt(d)

    blk = math.gcd(s, _SCAN_BLOCK)
    xs = [a.reshape(s // blk, blk, *a.shape[1:])
          for a in (q, k, v, jnp.arange(s))]
    _, o = jax.lax.scan(
        jax.checkpoint(lambda state, x: jax.lax.scan(step, state, x)),
        jnp.zeros((heads, d, v.shape[-1]), jnp.float32), xs)
    return o.reshape(s, heads, v.shape[-1])


def lightning(p: Group, a, *, heads: int, d: int, theta: float, eps: float,
              precision: str = "f32", forget: bool = False):
    """The linear-attention mixer on one sequence: a [S, H] -> [S, H]."""
    s = a.shape[0]

    def head(w, g):
        x = _mm(a, p[w], precision).reshape(s, heads, d)
        return rope(_norm(x, p[g], eps), theta, d)

    q, k = head("lin.wq", "lin.q_norm.g"), head("lin.wk", "lin.k_norm.g")
    v = _mm(a, p["lin.wv"], precision).reshape(s, heads, d)
    o = recurrence(_lower(q, precision), _lower(k, precision),
                   _lower(v, precision), forget).reshape(s, heads * d)
    o = _norm(o, p["lin.o_norm.g"], eps) * jax.nn.sigmoid(
        _mm(a, p["lin.wg"], precision))
    return _mm(o, p["lin.wo"], precision)


def forced_blocks(t, blocks: int, *, block_size: int, init_blocks: int,
                  window_size: int):
    """[len(t), blocks] bool: the blocks a token reads whatever their
    score, and the blocks it may read at all."""
    own = (t // block_size)[:, None]
    at = jnp.arange(blocks)[None, :]
    valid = at <= own
    forced = ((at < init_blocks) | (at > own - window_size // block_size))
    return forced & valid, valid


def select(q, k, *, kernel_size: int, kernel_stride: int, block_size: int,
           topk: int, init_blocks: int, window_size: int):
    """The rule: q [S, nq, d], k [S, nkv, d] -> [nkv, S, min(topk,
    blocks)] int32, a token's ids ascending, then ``blocks`` (padding)."""
    s, nq, d = q.shape
    nkv = k.shape[1]
    blocks = s // block_size
    kernels = (s - kernel_size) // kernel_stride + 1
    starts = kernel_stride * jnp.arange(kernels)
    kc = _means(k, kernels, kernel_size, kernel_stride)            # [J, nkv, d]
    # which kernels overlap which block
    at = block_size * jnp.arange(blocks)
    overlap = ((starts[None, :] < at[:, None] + block_size)
               & (starts[None, :] + kernel_size > at[:, None]))    # [Bk, J]
    picks = min(topk, blocks)
    qb = math.gcd(s, _QUERY_BLOCK)

    def one(c):
        t = c * qb + jnp.arange(qb)
        qc = jax.lax.dynamic_slice_in_dim(q, c * qb, qb).reshape(
            qb, nkv, nq // nkv, d)
        logits = jnp.einsum("tghd,jgd->tghj", qc, kc,
                            precision=HIGHEST) / math.sqrt(d)
        seen = (starts[None, :] + kernel_size - 1 <= t[:, None])   # [T, J]
        seen = seen[:, None, None, :]
        a = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
        a = jnp.sum(jnp.where(seen, a, 0.0), axis=2)              # [T, g, J]
        score = _pool(a, overlap)
        forced, valid = forced_blocks(
            t, blocks, block_size=block_size, init_blocks=init_blocks,
            window_size=window_size)
        score = jnp.where(forced[:, None, :], jnp.inf, score)
        score = jnp.where(valid[:, None, :], score, -jnp.inf)
        best, ids = jax.lax.top_k(score, picks)
        return jnp.sort(jnp.where(best > -jnp.inf, ids, blocks), axis=-1)

    out = jax.lax.map(one, jnp.arange(s // qb))          # [C, T, g, picks]
    return out.reshape(s, nkv, picks).transpose(1, 0, 2).astype(jnp.int32)


def _means(k, kernels, kernel_size, stride):
    """The keys' means under each kernel, as a gather of its rows."""
    rows = stride * jnp.arange(kernels)[:, None] + jnp.arange(kernel_size)
    return jnp.mean(k[rows], axis=1)                      # [J, nkv, d]


def _pool(a, overlap):
    """a [T, g, J], overlap [blocks, J] -> [T, g, blocks]: the max over a
    block's kernels, a block at a time (a scan: nothing [T, blocks, J])."""
    return jnp.moveaxis(jax.lax.map(
        lambda row: jnp.max(jnp.where(row[None, None, :], a, -jnp.inf), -1),
        overlap), 0, -1)


def sparse_attention(q, k, v, sets, *, block_size: int, precision: str):
    """q [S, nq, d], k / v [S, nkv, d], sets [nkv, S, picks] (ids >= the
    number of blocks: padding) -> [S, nq, d]: softmax over the keys i <= t
    in the set's blocks, a masked softmax one block of queries at a
    time."""
    s, nq, d = q.shape
    nkv = k.shape[1]
    blocks = s // block_size
    qb = math.gcd(s, _QUERY_BLOCK)
    q, k, v = (_lower(x, precision) for x in (q, k, v))

    def one(c):
        t = c * qb + jnp.arange(qb)
        qc = jax.lax.dynamic_slice_in_dim(q, c * qb, qb).reshape(
            qb, nkv, nq // nkv, d)
        mine = jax.lax.dynamic_slice_in_dim(sets, c * qb, qb, axis=1)
        member = jnp.any(mine[..., None] == jnp.arange(blocks), axis=2)
        visible = (jnp.repeat(member, block_size, axis=-1)     # [g, T, S]
                   & (jnp.arange(s)[None, :] <= t[:, None])[None])
        scores = jnp.einsum("tghd,kgd->gthk", qc, k,
                            precision=HIGHEST) / math.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(visible[:, :, None, :], scores, -jnp.inf), axis=-1)
        probs = jnp.where(visible[:, :, None, :], probs, 0.0)
        return jnp.einsum("gthk,kgd->tghd", _lower(probs, precision), v,
                          precision=HIGHEST)

    out = jax.lax.map(jax.checkpoint(one), jnp.arange(s // qb))
    return out.reshape(s, nq, d)


def sparse(p: Group, a, forced=None, *, nq: int, nkv: int, d: int,
           eps: float, rule: dict, precision: str = "f32",
           local: bool = False):
    """The sparse mixer on one sequence: a [S, H] -> ([S, H], (the
    reference's own sets [nkv, S, picks], how many blocks each holds))."""
    s = a.shape[0]
    blocks = s // rule["block_size"]
    q = _norm(_mm(a, p["wq"], precision).reshape(s, nq, d), p["q_norm.g"],
              eps)
    k = _norm(_mm(a, p["wk"], precision).reshape(s, nkv, d), p["k_norm.g"],
              eps)
    v = _mm(a, p["wv"], precision).reshape(s, nkv, d)
    own = jax.lax.stop_gradient(select(
        jax.lax.stop_gradient(q), jax.lax.stop_gradient(k), **rule))
    sets = own if forced is None else forced.astype(jnp.int32)
    if local:
        must, _ = forced_blocks(
            jnp.arange(s), blocks, block_size=rule["block_size"],
            init_blocks=rule["init_blocks"], window_size=rule["window_size"])
        sets = jnp.broadcast_to(jnp.where(
            must, jnp.arange(blocks)[None, :], blocks)[None], (nkv, s, blocks))
    o = sparse_attention(q, k, v, sets, block_size=rule["block_size"],
                         precision=precision).reshape(s, nq * d)
    o = o * jax.nn.sigmoid(_mm(a, p["wg"], precision))
    return _mm(o, p["wo"], precision), (own, jnp.sum(own < blocks, axis=-1))


def block(p: Group, fixed: Group, x, forced=None, *, layer: Layer, eps: float,
          scale: float, lin: dict, attn: dict, rule: dict,
          precision: str = "f32", fault: Optional[str] = None):
    """One decoder block on one sequence: x [S, H] -> ([S, H], routing or
    None). ``fixed``: the inherited walk's arrays that get no gradient
    (none here); ``lin`` / ``attn``: the sizes of the two mixers."""
    a = _norm(x, p["norm_in.g"], eps)
    routing = None
    if layer.sparse:
        mixed, routing = sparse(p, a, forced, eps=eps, rule=rule,
                                precision=precision, local=fault == "local",
                                **attn)
    else:
        mixed = lightning(p, a, eps=eps, precision=precision,
                          forget=fault == "forget", **lin)
    h = x + scale * mixed
    m = _norm(h, p["norm_post.g"], eps)
    rows = math.gcd(m.shape[0], _MLP_ROWS)
    f = jax.lax.map(       # [S, I] float32 is 1 GB at the cell's size
        jax.checkpoint(lambda mc: swiglu(mc, p["w1"], p["w3"], p["w2"],
                                         precision)),
        m.reshape(-1, rows, m.shape[1])).reshape(m.shape)
    return h + scale * f, routing


def head(p: Group, x, *, eps: float, divide: float, precision: str = "f32"):
    """x [S, H] -> logits [S, V]."""
    return _mm(_norm(x, p["norm_f.g"], eps) / divide, p["lm_head"],
               precision)


def _row_loss(p, x, labels, **kw):
    logp = jax.nn.log_softmax(head(p, x, **kw))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


class Reference(afmoe_reference.Reference):
    """The model by groups of weights made on demand, as
    ``afmoe_reference.Reference`` (whose walk over the blocks, forward
    and backward, this is): ``layers`` says what each held block is,
    ``lin`` and ``attn`` the two mixers' sizes, ``rule`` the selection's.
    ``precision``: ``"f32"``, ``"fp8"``, ``"forget"`` or ``"local"``
    (module docstring). ``follow``: for each training forward the
    [sparse blocks x kv groups, B, S, picks] sets its tokens read;
    ``routing`` holds, after a forward, each SPARSE block's (own sets [B,
    kv groups, S, picks], blocks each holds [B, kv groups, S])."""

    def __init__(self, get: Callable[[str], Group], *,
                 layers: Sequence[Layer], lin: dict, attn: dict, rule: dict,
                 eps: float, scale: float, embed_scale: float, divide: float,
                 follow: Optional[Iterable] = None, precision: str = "f32"):
        self.get, self.layers = get, list(layers)
        self.fixed = lambda block, step: {}
        self.embed_scale = embed_scale
        self._groups = attn["nkv"]
        self._follow = None if follow is None else iter(follow)
        fault = precision if precision in ("forget", "local") else None
        kw = dict(eps=eps, precision="f32" if fault else precision)
        self._block, self._block_bwd = {}, {}
        for layer in set(self.layers):
            blk = functools.partial(block, layer=layer, scale=scale, lin=lin,
                                    attn=attn, rule=rule, fault=fault, **kw)
            self._block[layer] = jax.jit(blk)
            self._block_bwd[layer] = jax.jit(self._bwd_of(blk))
        self._logits = jax.jit(functools.partial(head, divide=divide, **kw))
        loss = functools.partial(_row_loss, divide=divide, **kw)
        self._head_bwd = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        self.routing, self.first_routing = [], None
        self._forwards = 0       # training forwards made so far

    def block_params(self, get, i: int) -> Group:
        """Block i's weights under the names ``block`` knows."""
        return dict(get(f"h.{i}"),
                    **{name: get(f"h.{i}.{name}")["w"] for name in MLP})

    def loss_and_grads(self, get, ids, labels):
        """The inherited walk, a block's SwiGLU matrices yielded as
        groups of their own before the block's other leaves."""
        for group, grads in super().loss_and_grads(get, ids, labels):
            if group.startswith("h."):
                for name in reversed(MLP):
                    yield f"{group}.{name}", {"w": grads.pop(name)}
            yield group, grads

    def _forced(self, forced, i: int, r: int):
        """The sets row r reads in block i, [kv groups, S, picks]:
        ``forced`` counts the sparse blocks alone, a kv group at a time."""
        if forced is None or not self.layers[i].sparse:
            return None
        n = sum(l.sparse for l in self.layers[:i]) * self._groups
        return forced[n:n + self._groups, r]

    def _forward(self, get, ids, forced=None):
        """Activations entering each block, and after the last, per row
        (the inherited walk's, keeping a SPARSE block's routing)."""
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
            if layer.sparse:
                self.routing.append(tuple(
                    np.stack([np.asarray(o[1][k]) for o in outs])
                    for k in range(2)))
        if self.first_routing is None:
            self.first_routing = self.routing
        return xs
