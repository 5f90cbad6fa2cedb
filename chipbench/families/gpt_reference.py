"""The plain reference for the GPT-2/GPT-3 family.

Written from the published description (Radford et al. 2019; Brown et
al. 2020, arXiv:2005.14165 section 2.1): learned token and position
embeddings, pre-LayerNorm decoder blocks with dense causal multi-head
attention and a 4x GELU (erf) MLP, a final LayerNorm and an untied
output projection without bias. ``jax.numpy`` only, float32, matmuls at
``Precision.HIGHEST``, no kernels, no cache, no batching tricks. It
imports nothing of the program and takes no array the program made:
every weight is made again from the seed by ``get(group)``.

Departures, each for memory only: the training reference walks the
blocks one at a time, one batch row at a time (hand-chained ``jax.vjp``
per block instead of one ``jax.grad`` over the model), never holds a
parameter between uses, and keeps as its only optimizer state the first
step's gradient (AdamW's moments after one step are functions of it).
It follows the first TWO steps.

``precision="fp8"`` is the control of "How correct is decided": the same
reference with the operands of every matmul (attention's included)
rounded to float8-e4m3 with one scale per tensor; straight-through in
the backward pass. It must FAIL the comparison the program passes.

Weights by published name. ``embed``: ``wte`` [V, H], ``wpe`` [P, H].
Block ``h.<i>``: ``ln_1.g/b``, ``c_attn.w`` [H, 3H] (columns q|k|v, each
heads x d_head) ``/b``, ``c_proj.w/b``, ``ln_2.g/b``, ``c_fc.w`` [H, I]
``/b``, ``mlp_proj.w`` [I, H] ``/b``. ``head``: ``ln_f.g/b``, ``lm_head``
[H, V].
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Group = Dict[str, jax.Array]


def _lower(x, precision):
    """``x`` as a lower precision would hold it (the backward pass sees
    the identity): ``fp8`` scales the whole tensor into float8-e4m3's
    range and rounds to its three mantissa bits."""
    if precision == "f32":
        return x
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, w, precision):
    return jnp.matmul(_lower(a, precision), _lower(w, precision),
                      precision=HIGHEST)


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def embed(p: Group, ids):
    """ids [S] -> [S, H]."""
    return p["wte"][ids] + p["wpe"][jnp.arange(ids.shape[0])]


def block(p: Group, x, *, heads: int, eps: float, precision: str = "f32"):
    """One pre-LN decoder block on one sequence: x [S, H] -> [S, H]."""
    s, h = x.shape
    d = h // heads
    a = _layer_norm(x, p["ln_1.g"], p["ln_1.b"], eps)
    qkv = _mm(a, p["c_attn.w"], precision) + p["c_attn.b"]
    q, k, v = (t.reshape(s, heads, d).transpose(1, 0, 2)
               for t in jnp.split(qkv, 3, axis=-1))        # [heads, S, d]
    q, k = _lower(q, precision), _lower(k, precision)
    scores = jnp.einsum("hqd,hkd->hqk", q, k, precision=HIGHEST)
    scores = scores / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs, v = _lower(probs, precision), _lower(v, precision)
    o = jnp.einsum("hqk,hkd->hqd", probs, v, precision=HIGHEST)
    o = o.transpose(1, 0, 2).reshape(s, h)
    x = x + _mm(o, p["c_proj.w"], precision) + p["c_proj.b"]
    m = _layer_norm(x, p["ln_2.g"], p["ln_2.b"], eps)
    m = jax.nn.gelu(_mm(m, p["c_fc.w"], precision) + p["c_fc.b"],
                    approximate=False)
    return x + _mm(m, p["mlp_proj.w"], precision) + p["mlp_proj.b"]


def head(p: Group, x, *, eps: float, precision: str = "f32"):
    """x [S, H] -> logits [S, V]."""
    return _mm(_layer_norm(x, p["ln_f.g"], p["ln_f.b"], eps),
               p["lm_head"], precision)


def _row_loss(p, x, labels, *, eps, precision):
    """Mean next-token cross-entropy of one sequence."""
    logp = jax.nn.log_softmax(head(p, x, eps=eps, precision=precision))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


class Reference:
    """The model by groups of weights made on demand.

    ``get(group)`` returns the float32 weights of ``"embed"``,
    ``"h.<i>"`` or ``"head"``; nothing is kept between calls.
    """

    def __init__(self, get: Callable[[str], Group], *, layers: int,
                 heads: int, eps: float, precision: str = "f32"):
        self.get, self.layers = get, layers
        kw = dict(eps=eps, precision=precision)
        blk = functools.partial(block, heads=heads, **kw)
        self._embed = jax.jit(embed)
        self._block = jax.jit(blk)

        def head_stats(p, x, tokens):
            rows = head(p, x, **kw)
            chosen = jnp.take_along_axis(rows, tokens[:, None], axis=1)[:, 0]
            return rows.max(axis=-1), chosen, rows.argmax(axis=-1)

        self._head_stats = jax.jit(head_stats)
        loss = functools.partial(_row_loss, **kw)
        self._head_bwd = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

        def block_bwd(p, x, dy):
            _, vjp = jax.vjp(blk, p, x)
            return vjp(dy)

        def embed_bwd(p, ids, dx):
            _, vjp = jax.vjp(lambda q: embed(q, ids), p)
            return vjp(dx)[0]

        self._block_bwd = jax.jit(block_bwd)
        self._embed_bwd = jax.jit(embed_bwd)

    # -- serving: logits of one sequence --------------------------------
    def position_stats(self, ids: np.ndarray, tokens: np.ndarray):
        """One forward over the sequence ``ids``. Per position: the
        largest logit, the logit of ``tokens[position]``, and the token
        the reference puts first (the [S, V] logits stay on the device)."""
        x = self._embed(self.get("embed"), jnp.asarray(ids, jnp.int32))
        for i in range(self.layers):
            x = self._block(self.get(f"h.{i}"), x)
        best, chosen, first = self._head_stats(
            self.get("head"), x, jnp.asarray(tokens, jnp.int32))
        return np.asarray(best), np.asarray(chosen), np.asarray(first)

    # -- training: loss and gradients, group by group -------------------
    def loss_and_grads(self, get, ids: np.ndarray, labels: np.ndarray):
        """Generator over one batch [B, S]: yields ``(group, grads)``
        from ``head`` down to ``embed`` (the caller consumes and drops
        each), and sets ``self.loss`` to the batch's mean loss."""
        rows = ids.shape[0]
        ids = jnp.asarray(ids, jnp.int32)
        labels = jnp.asarray(labels, jnp.int32)
        p = get("embed")
        xs = [[self._embed(p, ids[r]) for r in range(rows)]]
        for i in range(self.layers):
            p = get(f"h.{i}")
            xs.append([self._block(p, x) for x in xs[-1]])
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
        for i in reversed(range(self.layers)):
            p, grads = get(f"h.{i}"), None
            for r in range(rows):
                g, dxs[r] = self._block_bwd(p, xs[i][r], dxs[r])
                grads = g if grads is None else add(grads, g)
            xs.pop()
            yield f"h.{i}", grads
        p, grads = get("embed"), None
        for r in range(rows):
            g = self._embed_bwd(p, ids[r], dxs[r])
            grads = g if grads is None else add(grads, g)
        yield "embed", grads


def _norms(tree: Group) -> Dict[str, float]:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v))))
            for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("step",))
def _adamw(p, m, v, g, *, step, lr, beta1, beta2, eps, weight_decay):
    """AdamW (Loshchilov & Hutter, arXiv:1711.05101 algorithm 2) on one
    group, decoupled decay on every parameter, in float32."""
    def one(p, m, v, g):
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g * g
        mhat = m / (1 - beta1 ** step)
        vhat = v / (1 - beta2 ** step)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p), m, v
    out = {k: one(p[k], m[k], v[k], g[k]) for k in p}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def _rounding_variance(t):
    """Summed variance of rounding the float32 ``t`` stochastically to
    bfloat16: an element between the neighbours ``lo`` and ``hi`` goes up
    with probability ``(t - lo) / (hi - lo)``, so its variance is
    ``(t - lo) * (hi - t)``."""
    cut = jax.lax.bitcast_convert_type(t, jnp.uint32) & jnp.uint32(0xFFFF0000)
    lo = jax.lax.bitcast_convert_type(cut, jnp.float32)
    hi = jax.lax.bitcast_convert_type(cut + jnp.uint32(0x10000), jnp.float32)
    return jnp.sum((t - lo) * (hi - t))


@functools.partial(jax.jit, static_argnames=("noisy",))
def _change_norms(p0, p1, p2, *, noisy):
    """L2 norm of each parameter's change over the two steps as the
    configuration's storage gives it. ``noisy`` (bfloat16 parameters
    written back with stochastic rounding): each write adds zero-mean
    noise of known variance, so the expected squared norm is that of the
    exact change plus both writes' variances (the second write starts
    from a stored value: the first result on the bfloat16 grid)."""
    out = {}
    for k in p2:
        sq = jnp.sum(jnp.square(p2[k] - p0[k]))
        if noisy:
            stored = p1[k].astype(jnp.bfloat16).astype(jnp.float32)
            sq += (_rounding_variance(p1[k])
                   + _rounding_variance(stored + (p2[k] - p1[k])))
        out[k] = jnp.sqrt(sq)
    return out


def train_two_steps(ref: Reference, batches, hyper: Dict,
                    storage: str) -> Dict:
    """Follow the first two AdamW steps from the seeded weights.

    ``batches``: two ``(ids, labels)`` pairs; ``hyper``: the
    configuration's ``optimizer`` group; ``storage``: the type the
    parameters are held in. Returns ``loss`` (two numbers),
    ``grad_norm`` (first gradient, ``group/name`` -> L2 norm),
    ``delta_norm`` (L2 norm of each parameter's change after the two
    steps, see ``_change_norms``) and ``moment_norm`` (L2 norms of both
    AdamW moments after the two steps, ``m/group/name`` and
    ``v/group/name``). The only state kept between the steps is the
    first gradient, and it waits on the host: on the device it alone
    (5.7 GB at 1.4B parameters) would set the process's memory peak,
    which has to stay the program's. A group's parameters after step one
    are made again from the seeded weights and that gradient."""
    noisy = bool(hyper["stochastic_rounding"]) and storage == "bfloat16" \
        and not hyper["master_weights"]
    hyper = dict(lr=hyper["lr"], beta1=hyper["beta1"], beta2=hyper["beta2"],
                 eps=hyper["eps"], weight_decay=hyper["weight_decay"])
    zeros = functools.partial(jax.tree_util.tree_map, jnp.zeros_like)
    first: Dict[str, Group] = {}
    out = {"loss": [], "grad_norm": {}, "delta_norm": {}, "moment_norm": {}}

    ids, labels = batches[0]
    for group, g in ref.loss_and_grads(ref.get, ids, labels):
        first[group] = jax.device_get(g)   # waits on the host, see above
        for k, n in _norms(g).items():
            out["grad_norm"][f"{group}/{k}"] = n
    out["loss"].append(ref.loss)

    def after_one(group):
        p0 = ref.get(group)
        z = zeros(p0)
        return _adamw(p0, z, z, first[group], step=1, **hyper)

    ids, labels = batches[1]
    for group, g in ref.loss_and_grads(lambda grp: after_one(grp)[0],
                                       ids, labels):
        p1, m1, v1 = after_one(group)
        p2, m2, v2 = _adamw(p1, m1, v1, g, step=2, **hyper)
        change = _change_norms(ref.get(group), p1, p2, noisy=noisy)
        for k in p2:
            out["delta_norm"][f"{group}/{k}"] = float(change[k])
        for tag, tree in (("m", m2), ("v", v2)):
            for k, n in _norms(tree).items():
                out["moment_norm"][f"{tag}/{group}/{k}"] = n
        del first[group]
    out["loss"].append(ref.loss)
    return out


def served_sequence(prompt: np.ndarray, served: np.ndarray,
                    pad_to: int) -> Tuple[np.ndarray, np.ndarray, slice]:
    """``prompt + served[:-1]`` right-padded to ``pad_to`` positions
    (which causal attention never looks at), the served token to read at
    each position, and the slice of positions that produced them."""
    n_p, n_s = len(prompt), len(served)
    seq = np.zeros((pad_to,), np.int32)
    seq[:n_p] = prompt
    seq[n_p:n_p + n_s - 1] = served[:-1]
    tokens = np.zeros((pad_to,), np.int32)
    tokens[n_p - 1:n_p - 1 + n_s] = served
    return seq, tokens, slice(n_p - 1, n_p - 1 + n_s)


def served_gaps(ref: Reference, prompt, served, pad_to: int) -> np.ndarray:
    """Per served token, the gap by which its logit lies below the
    reference's best at that position (0 = the reference's own choice)."""
    seq, tokens, where = served_sequence(prompt, served, pad_to)
    best, chosen, _ = ref.position_stats(seq, tokens)
    return (best - chosen)[where]


def control_gaps(ref: Reference, low: Reference, prompt, served,
                 pad_to: int) -> np.ndarray:
    """The control's reading at the same positions: the gap, under
    ``ref``, of the token that the lower-precision ``low`` puts first."""
    seq, tokens, where = served_sequence(prompt, served, pad_to)
    _, _, first = low.position_stats(seq, tokens)
    best, chosen, _ = ref.position_stats(seq, first)
    return (best - chosen)[where]
