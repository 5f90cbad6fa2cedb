"""Routed experts a model can hold without ``paddle_tpu.distributed``.

``RoutedExperts``: E gated experts stacked ``[E, ...]``, their
activation a value the family DECLARES (``activation``: a name of
``ACTIVATIONS`` — SwiGLU the default, ReGLU — or a function from the
gate-up product and the width), run DROPLESS — every (token, choice)
pair is computed, whatever the routing: the tokens are sorted by expert
(stable), the sizes of the groups are a ``bincount``, both expert
matmuls are grouped matmuls over the sorted rows
(``ops.grouped_matmul``: no capacity, no ``[E, C, H]`` padding buffer,
no dispatch mode), and the inverse permutation puts the rows back. The
layer takes the router's choice — expert ids and gate values
``[T, k]`` — and returns the combined output ``[T, H]``. Told that it
holds a SHARE of the experts the router chooses among (``held`` of
``num_experts`` from ``first`` on: one chip's part of an expert-parallel
layer), it computes its own experts' part for the pairs routed to them,
dropless, whatever number that is: the pairs of the absent experts sort
last, belong to no group, are never computed and add zero. No code
stands in for the absent chips or their traffic.

A share computes a BOUNDED number of rows at a time. The sorted order
puts the held experts' pairs first, so a static window of it holds all
of them whenever their number (``sum(sizes)``, on the device) is at most
the window's length: ``row_bound``, one even share of the layer's pairs
and an excess that falls with the square root of the number of experts
held, rounded up to the row tile — 4,096 rows (4 even shares) for 32,768
pairs where 8 of 256 experts are held, 42,240 (2.06) for 163,840 where
64 of 512 are. The share's path is a loop over such windows for as many
as hold its pairs (one, unless a routing sends it more than its bound;
then two, three, ...: no pair is dropped at any routing): a
window gathers its rows straight from ``x``, runs the grouped matmuls and
the activation over them and adds each row times its pair's gate into its
token's row in float32. The trip count is data, so the loop is the
share's own custom VJP: the backward walks the same windows, runs each
one's forward again and adds up the gradients; a step holds no value of
every pair's rows anywhere. Where the bound would not halve the rows, and
where every expert is held, there is no loop and the layer traces as it
did without one (a row for every pair).

``MLPRouter``: a down-projection and a three-layer GELU MLP to E logits,
computed in float32 whatever the model's storage type, with a selection
bias ``beta`` (a float32 buffer: no gradient reaches it) that moves
WHICH expert is chosen and never the gate value. Top-1. Nothing here
moves ``beta``: a balancing rule is its owner's to run.

``SigmoidTopKRouter``: one float32 matrix to E scores ``sigmoid(m W)``,
the k best of ``score + bias`` chosen (``bias`` a float32 buffer that
enters the choice alone), the chosen scores renormalised to sum to one
and scaled: the gates.

(``distributed.fleet.meta_parallel.moe.MoELayer`` — capacity-dropping
``TopKGate``, two-matrix GELU experts, expert parallelism over a mesh —
is the older of the two and is left as it is; ROADMAP D5.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...base.tape import apply
from ...base.tensor import Tensor
from .. import initializer as I
from .layers import Layer

__all__ = ["RoutedExperts", "MLPRouter", "SigmoidTopKRouter",
           "SoftmaxTopKRouter"]

_HIGHEST = jax.lax.Precision.HIGHEST


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation ``perm`` with inverse ``inverse``:
    the gradient is a gather too (``g[inverse]``), where XLA's transpose
    of a gather would be a scatter-add."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return g[inverse], None, None


_permute_rows.defvjp(_permute_fwd, _permute_bwd)


def _zero_tail(a, rows):
    from ...ops.grouped_matmul import zero_tail
    return zero_tail(a, rows)


@jax.custom_vjp
def _result_tail(a, rows):
    """A grouped matmul's result with the rows its kernels left unwritten
    (from ``rows`` on) read as zero. The cotangent passes as it comes: it
    is finite, and the kernels that take it visit no row of the tail
    (``moe_tgmm`` selects its ``dy`` side row by row)."""
    return _zero_tail(a, rows)


_result_tail.defvjp(lambda a, rows: (_zero_tail(a, rows), None),
                    lambda _, g: (g, None))


@jax.custom_vjp
def _gradient_tail(a, rows):
    """``a`` as it is (its tail is finite: the tokens' own rows); its
    GRADIENT's rows from ``rows`` on, which the input-gradient kernel
    left unwritten, read as zero."""
    return a


_gradient_tail.defvjp(lambda a, rows: (a, rows),
                      lambda rows, g: (_zero_tail(g, rows), None))


# A share's load is a sum over the experts it holds, so its spread about
# one even share of the layer's pairs falls as 1 / sqrt(held). A window is
# one even share plus ``_EXCESS`` shares at ``_EXCESS_AT`` held experts;
# a routing that sends the share more takes a window more (the loop is
# exact at any number). From the records, not a knob (PERF.md section 6):
# 8 held experts of 256 under a balancing rule carry 1.1-1.5 even shares
# over a training window but pass 2 in a tenth of their calls and reach
# 3.1-3.9 in the first steps on seeded weights, so their window is 4; 64
# of 512 with no rule stay at 1.00-1.02, and theirs is 2.06. Every pass
# that is not a kernel pays for a window's whole length.
_EXCESS, _EXCESS_AT = 3, 8
_ROW_TILE = 256    # a multiple of it satisfies ``gmm_tiling`` and ``tgmm_tiling``


def row_bound(pairs: int, held: int, num_experts: int):
    """The static bound on the rows a share of ``held`` of ``num_experts``
    computes at a time for ``pairs`` (token, choice) pairs:
    ``1 + _EXCESS * sqrt(_EXCESS_AT / held)`` even shares, rounded up to
    the row tile. None where the bound would not halve the rows (or every
    expert is held, or none): a row for every pair, no loop."""
    if not 0 < held < num_experts:
        return None
    shares = 1 + _EXCESS * math.sqrt(_EXCESS_AT / held)
    rows = math.ceil(shares * pairs * held / num_experts)
    cap = -(-rows // _ROW_TILE) * _ROW_TILE
    return cap if 2 * cap <= pairs else None


def _swiglu(gu, f: int):
    return (jax.nn.silu(gu[:, :f].astype(jnp.float32))
            * gu[:, f:].astype(jnp.float32)).astype(gu.dtype)


def _reglu(gu, f: int):
    return (jax.nn.relu(gu[:, :f].astype(jnp.float32))
            * gu[:, f:].astype(jnp.float32)).astype(gu.dtype)


# gu [rows, 2F] (gate | up), F -> the hidden rows [rows, F]. Whatever is
# declared maps a zero row to a zero row: the rows a share's kernels left
# unwritten are read as zero before it and must stay zero after it.
ACTIVATIONS = {"swiglu": _swiglu, "reglu": _reglu}


def _every_pair(x, w_gu, w_dn, gates, order, inverse, sizes, share: bool,
                act):
    """A row for every (token, choice) pair, whatever the routing."""
    from ...ops.grouped_matmul import grouped_matmul

    t, k = gates.shape
    with jax.named_scope("moe.permute"):
        rows = x if k == 1 else jnp.repeat(x, k, axis=0)
        rows = _permute_rows(rows, order, inverse)
    if share:
        # a select costs a pass over all T*k rows (~1 ms at the cell's
        # size), so only where a kernel's unwritten rows would be read:
        # each result in the forward (``act`` must be finite where
        # ``moe_tgmm`` takes it as ``x``), the rows' gradient in the backward
        total = jnp.sum(sizes)
        rows = _gradient_tail(rows, total)
        result = lambda a: _result_tail(a, total)
    else:
        result = lambda a: a
    with jax.named_scope("moe.experts"):
        gu = result(grouped_matmul(rows, w_gu, sizes))
        y = result(grouped_matmul(act(gu, w_dn.shape[1]), w_dn, sizes))
    with jax.named_scope("moe.combine"):
        y = _permute_rows(y, inverse, order).reshape(t, k, -1)
        out = jnp.sum(y.astype(jnp.float32)
                      * gates.astype(jnp.float32)[..., None], axis=1)
    return out.astype(x.dtype)


def _window(order, sizes, cap: int, i):
    """Window ``i`` of the sorted order, ``cap`` pairs long: (the pairs
    [cap], the rows each group has inside it [E], their sum)."""
    lo = i * cap
    ends = jnp.cumsum(sizes)
    inside = (jnp.clip(ends, lo, lo + cap)
              - jnp.clip(ends - sizes, lo, lo + cap)).astype(jnp.int32)
    return jax.lax.dynamic_slice(order, (lo,), (cap,)), inside, jnp.sum(inside)


def _window_rows(rows, w_gu, w_dn, gates, pairs, sizes, total, act):
    """A window's rows (the tokens' own, gathered straight from ``x``) to
    what each adds to its token's output row [cap, H] float32: the two
    grouped matmuls with their tails (a select costs a pass over the rows,
    so only where a kernel's unwritten rows would be read), then each row
    times its pair's gate."""
    from ...ops.grouped_matmul import grouped_matmul

    with jax.named_scope("moe.experts"):
        gu = _result_tail(grouped_matmul(rows, w_gu, sizes), total)
        y = _result_tail(grouped_matmul(
            act(gu, w_dn.shape[1]), w_dn, sizes), total)
    with jax.named_scope("moe.combine"):
        of_row = gates.reshape(-1)[pairs].astype(jnp.float32)
        return y.astype(jnp.float32) * of_row[:, None]


def _windows(cap: int, order, sizes):
    """(how many windows of ``cap`` pairs hold the held experts' pairs,
    the sorted order padded to whole windows)."""
    return ((jnp.sum(sizes) + cap - 1) // cap,
            jnp.pad(order, (0, -order.shape[0] % cap)))


# Jitted, as the flash kernels' launchers are, so that a model's layers
# share ONE trace and ONE lowering of the two loops.
@functools.partial(jax.jit, static_argnums=(0, 1))
def _share_forward(cap, act, x, w_gu, w_dn, gates, order, sizes):
    k = gates.shape[1]
    count, order = _windows(cap, order, sizes)

    def window(i, out):
        pairs, inside, total = _window(order, sizes, cap, i)
        with jax.named_scope("moe.permute"):
            rows = x[pairs // k]
        h = _window_rows(rows, w_gu, w_dn, gates, pairs, inside, total, act)
        with jax.named_scope("moe.combine"):
            return out.at[pairs // k].add(h)

    out = jax.lax.fori_loop(0, count, window,
                            jnp.zeros(x.shape, jnp.float32))
    return out.astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _share_backward(cap, act, x, w_gu, w_dn, gates, order, sizes, g):
    k = gates.shape[1]
    count, order = _windows(cap, order, sizes)

    def window(i, grads):
        pairs, inside, total = _window(order, sizes, cap, i)
        with jax.named_scope("moe.permute"):
            rows = x[pairs // k]
        _, pull = jax.vjp(                     # the window's forward again
            lambda *a: _window_rows(*a, pairs, inside, total, act),
            rows, w_gu, w_dn, gates)
        with jax.named_scope("moe.combine"):
            d, *new = pull(g[pairs // k].astype(jnp.float32))
        with jax.named_scope("moe.permute"):
            # the input-gradient kernel leaves the rows past the window's
            # last group unwritten
            dx = grads[0].at[pairs // k].add(
                _zero_tail(d, total).astype(jnp.float32))
        return (dx, *[a + b for a, b in zip(grads[1:], new)])

    dx, *rest = jax.lax.fori_loop(
        0, count, window,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(w_gu),
         jnp.zeros_like(w_dn), jnp.zeros_like(gates)))
    return (dx.astype(x.dtype), *rest)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bounded_share(cap, act, x, w_gu, w_dn, gates, order, sizes):
    """A share's output, window by window of ``cap`` pairs of the sorted
    order for as many windows as hold the held experts' pairs — one,
    unless a routing sends the share more than its bound. The trip count
    is on the device, so the loop has a backward of its own: the same
    windows again, each running its forward once more (the residuals are
    the inputs) and adding its part of every gradient."""
    return _share_forward(cap, act, x, w_gu, w_dn, gates, order, sizes)


_bounded_share.defvjp(
    lambda cap, act, *args: (_share_forward(cap, act, *args), args),
    lambda cap, act, args, g: (
        *_share_backward(cap, act, *args, g), None, None))


def routed_experts(x, w_gu, w_dn, ids, gates, num_experts: int, first: int,
                   act=_swiglu):
    """x [T, H], w_gu [E, H, 2F] (gate | up), w_dn [E, F, H], ids / gates
    [T, k] -> (out [T, H], tokens per expert [E] int32). ``ids`` run over
    ``num_experts``; the E held ones are ``first .. first + E``; ``act``
    is the experts' activation (``ACTIVATIONS``)."""
    t, k = ids.shape
    held = w_gu.shape[0]
    share = held < num_experts
    cap = row_bound(t * k, held, num_experts)
    with jax.named_scope("moe.permute"):
        flat = ids.reshape(t * k)
        if share:   # the absent experts' pairs sort last, in no group
            flat = jnp.where((flat >= first) & (flat < first + held),
                             flat - first, held)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        if cap is None:
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
        sizes = jnp.bincount(flat, length=held + 1 if share else held)
        sizes = (sizes[:held] if share else sizes).astype(jnp.int32)
    if cap is None:
        out = _every_pair(x, w_gu, w_dn, gates, order, inverse, sizes, share,
                          act)
    else:
        out = _bounded_share(cap, act, x, w_gu, w_dn, gates, order, sizes)
    return out, sizes


class RoutedExperts(Layer):
    """Stacked gated experts, dropless. ``forward(x [.., H], ids [.., k],
    gates [.., k])`` -> [.., H]. ``held`` of the ``num_experts`` the ids
    run over live here, from ``first`` on (default: all of them).
    ``activation``: a name of ``ACTIVATIONS`` or a function ``(gu [rows,
    2F], F) -> [rows, F]`` that keeps a zero row zero.
    ``tokens_per_expert`` (int32 [held], a buffer on the device) adds up
    how many rows each held expert was given, call by call; a layer that
    holds a share also adds up ``pairs_routed``, every (token, choice)
    pair it saw (its rows no longer add up to them), and
    ``calls_in_full``, the calls in which the held experts' pairs passed
    ``row_bound`` and took more than one window of rows (all of its calls
    where the shapes allow no bound: a row for every pair), and keeps
    ``rows_a_window``, the rows ONE pass of its last traced call ran over
    (the permute, both grouped matmuls' operands, the activation, the
    combine: ``row_bound``'s window, or a row for every pair) — a Python
    int, known when the call is traced, no op on the device. Nothing
    reads them back but whoever asks (``numpy()``)."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int, held: int = None, first: int = 0,
                 activation="swiglu"):
        super().__init__()
        held = num_experts if held is None else held
        if not 0 <= first <= first + held <= num_experts:
            raise ValueError(f"experts {first}..{first + held} of "
                             f"{num_experts}")
        self.num_experts, self.first = num_experts, first
        self.activation = (activation if callable(activation)
                           else ACTIVATIONS[activation])
        init = I.Normal(0.0, 0.02)
        self.w_gu = self.create_parameter(
            [held, hidden_size, 2 * intermediate_size],
            default_initializer=init)
        self.w_dn = self.create_parameter(
            [held, intermediate_size, hidden_size],
            default_initializer=init)
        self.register_buffer("tokens_per_expert", Tensor(
            jnp.zeros([held], jnp.int32), _internal=True))
        if held < num_experts:
            self.rows_a_window = None
            for name in ("pairs_routed", "calls_in_full"):
                self.register_buffer(name, Tensor(
                    jnp.zeros([], jnp.int32), _internal=True))

    def compute(self, x, ids, gates):
        """The layer without its counters: (out, rows each held expert
        got [held]). For a caller that runs it where a buffer cannot be
        written (under ``recompute``) and calls ``count`` after."""
        lead, h = tuple(x.shape[:-1]), x.shape[-1]
        k = ids.shape[-1]

        def run(x, w_gu, w_dn, ids, gates):
            out, sizes = routed_experts(
                x.reshape(-1, h), w_gu, w_dn, ids.reshape(-1, k),
                gates.reshape(-1, k), self.num_experts, self.first,
                self.activation)
            return out.reshape(*lead, h), sizes

        return apply(run, x, self.w_gu, self.w_dn, ids, gates,
                     op_name="routed_experts")

    def count(self, sizes, pairs: int) -> None:
        self.tokens_per_expert.set_value(
            self.tokens_per_expert._data + sizes._data)
        if "pairs_routed" in self._buffers:
            self.pairs_routed.set_value(self.pairs_routed._data + pairs)
            cap = row_bound(pairs, sizes.shape[0], self.num_experts)
            self.rows_a_window = pairs if cap is None else cap
            in_full = 1 if cap is None else jnp.sum(sizes._data) > cap
            self.calls_in_full.set_value(self.calls_in_full._data + in_full)

    def forward(self, x, ids, gates):
        out, sizes = self.compute(x, ids, gates)
        self.count(sizes, math.prod(ids.shape))
        return out


def mlp_router(w, beta, wd, bd, w1, b1, w2, b2, w3, b3):
    """w [.., H] -> (ids [.., 1] int32, gates [.., 1] float32): all of it
    in float32 at full matmul precision; ``beta`` [E] enters the choice
    alone."""
    f32 = jnp.float32

    def mm(a, m):
        return jnp.matmul(a, m.astype(f32), precision=_HIGHEST)

    def gelu(a):
        return jax.nn.gelu(a, approximate=False)

    r = mm(w.astype(f32), wd) + bd.astype(f32)
    a = gelu(mm(r, w1) + b1.astype(f32))
    a = gelu(mm(a, w2) + b2.astype(f32))
    probs = jax.nn.softmax(mm(a, w3) + b3.astype(f32), axis=-1)
    ids = jnp.argmax(probs + jax.lax.stop_gradient(beta.astype(f32)),
                     axis=-1).astype(jnp.int32)[..., None]
    return ids, jnp.take_along_axis(probs, ids, axis=-1)


class MLPRouter(Layer):
    """``forward(w [.., H])`` -> (ids [.., 1], gates [.., 1]): top-1 of
    ``softmax(W3 gelu(W2 gelu(W1 (w Wd + bd) + b1) + b2) + b3)``; the
    chosen expert is the argmax of probabilities + ``beta``, the gate
    value the chosen expert's probability without it."""

    def __init__(self, hidden_size: int, router_hidden_size: int,
                 num_experts: int):
        super().__init__()
        r, init = router_hidden_size, I.Normal(0.0, 0.02)
        shapes = (("wd", [hidden_size, r]), ("w1", [r, r]), ("w2", [r, r]),
                  ("w3", [r, num_experts]))
        for name, shape in shapes:
            setattr(self, name, self.create_parameter(
                shape, default_initializer=init))
            setattr(self, "b" + name[1:], self.create_parameter(
                shape[1:], is_bias=True))
        self.register_buffer("beta", Tensor(
            jnp.zeros([num_experts], jnp.float32), _internal=True))

    def forward(self, w):
        return apply(mlp_router, w, self.beta, self.wd, self.bd, self.w1,
                     self.b1, self.w2, self.b2, self.w3, self.b3,
                     op_name="mlp_router")


def sigmoid_topk_router(m, w, bias, *, top_k: int, scale: float, norm: bool):
    """m [.., H] -> (ids [.., k] int32, gates [.., k] float32): scores
    ``sigmoid(m w)`` in float32 at full matmul precision; the ``top_k``
    best of score + ``bias`` [E] are chosen (best first), the gate of a
    chosen expert is its score without the bias, over the chosen scores'
    sum under ``norm``, times ``scale``."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.matmul(m.astype(f32), w.astype(f32),
                                       precision=_HIGHEST))
    _, ids = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(f32)), top_k)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    if norm:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), scale * gates


class SigmoidTopKRouter(Layer):
    """``forward(m [.., H])`` -> (ids [.., k], gates [.., k]): the k
    experts of the largest ``sigmoid(m W) + bias``; gates
    ``route_scale * score / sum of the chosen scores`` (``route_norm``).
    ``bias`` is a float32 buffer: nothing here moves it."""

    def __init__(self, hidden_size: int, num_experts: int, top_k: int,
                 route_scale: float = 1.0, route_norm: bool = True):
        super().__init__()
        self.top_k, self.scale, self.norm = top_k, route_scale, route_norm
        self.weight = self.create_parameter(
            [hidden_size, num_experts],
            default_initializer=I.Normal(0.0, 0.02))
        self.register_buffer("bias", Tensor(
            jnp.zeros([num_experts], jnp.float32), _internal=True))

    def forward(self, m):
        return apply(
            lambda m, w, b: sigmoid_topk_router(
                m, w, b, top_k=self.top_k, scale=self.scale, norm=self.norm),
            m, self.weight, self.bias, op_name="sigmoid_topk_router")


def softmax_topk_router(m, w, *, top_k: int, norm: bool):
    """m [.., H] -> (ids [.., k] int32, gates [.., k] float32):
    probabilities ``softmax(m w)`` over ALL the experts in float32 at full
    matmul precision; the ``top_k`` largest are chosen (best first), the
    gate of a chosen expert is its probability, over the chosen ones' sum
    under ``norm``."""
    f32 = jnp.float32
    probs = jax.nn.softmax(jnp.matmul(m.astype(f32), w.astype(f32),
                                      precision=_HIGHEST), axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    if norm:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), gates


class SoftmaxTopKRouter(Layer):
    """``forward(m [.., H])`` -> (ids [.., k], gates [.., k]): softmax
    over all the experts, THEN the k largest, their probabilities
    renormalised to sum to one (``norm_topk_prob``)."""

    def __init__(self, hidden_size: int, num_experts: int, top_k: int,
                 norm_topk_prob: bool = True):
        super().__init__()
        self.top_k, self.norm = top_k, norm_topk_prob
        self.weight = self.create_parameter(
            [hidden_size, num_experts],
            default_initializer=I.Normal(0.0, 0.02))

    def forward(self, m):
        return apply(
            lambda m, w: softmax_topk_router(
                m, w, top_k=self.top_k, norm=self.norm),
            m, self.weight, op_name="softmax_topk_router")
