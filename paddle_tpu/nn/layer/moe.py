"""Routed experts a model can hold without ``paddle_tpu.distributed``.

``RoutedExperts``: E gated (SwiGLU) experts stacked ``[E, ...]``, run
DROPLESS — every (token, choice) pair is computed, whatever the routing:
the tokens are sorted by expert (stable), the sizes of the groups are a
``bincount``, both expert matmuls are grouped matmuls over the sorted
rows (``ops.grouped_matmul``: no capacity, no ``[E, C, H]`` padding
buffer, no dispatch mode), and the inverse permutation puts the rows
back. The layer takes the router's choice — expert ids and gate values
``[T, k]`` — and returns the combined output ``[T, H]``.

``MLPRouter``: a down-projection and a three-layer GELU MLP to E logits,
computed in float32 whatever the model's storage type, with a selection
bias ``beta`` (a float32 buffer: no gradient reaches it) that moves
WHICH expert is chosen and never the gate value. Top-1. Nothing here
moves ``beta``: a balancing rule is its owner's to run.

(``distributed.fleet.meta_parallel.moe.MoELayer`` — capacity-dropping
``TopKGate``, two-matrix GELU experts, expert parallelism over a mesh —
is the older of the two and is left as it is; ROADMAP D5.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...base.tape import apply
from ...base.tensor import Tensor
from .. import initializer as I
from .layers import Layer

__all__ = ["RoutedExperts", "MLPRouter"]

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


def routed_experts(x, w_gu, w_dn, ids, gates):
    """x [T, H], w_gu [E, H, 2F] (gate | up), w_dn [E, F, H], ids / gates
    [T, k] -> (out [T, H], tokens per expert [E] int32)."""
    from ...ops.grouped_matmul import grouped_matmul

    t, k = ids.shape
    experts, f = w_gu.shape[0], w_dn.shape[1]
    with jax.named_scope("moe.permute"):
        flat = ids.reshape(t * k)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
        sizes = jnp.bincount(flat, length=experts).astype(jnp.int32)
        rows = x if k == 1 else jnp.repeat(x, k, axis=0)
        rows = _permute_rows(rows, order, inverse)
    with jax.named_scope("moe.experts"):
        gu = grouped_matmul(rows, w_gu, sizes)
        act = (jax.nn.silu(gu[:, :f].astype(jnp.float32))
               * gu[:, f:].astype(jnp.float32)).astype(x.dtype)
        y = grouped_matmul(act, w_dn, sizes)
    with jax.named_scope("moe.combine"):
        y = _permute_rows(y, inverse, order).reshape(t, k, -1)
        out = jnp.sum(y.astype(jnp.float32)
                      * gates.astype(jnp.float32)[..., None], axis=1)
    return out.astype(x.dtype), sizes


class RoutedExperts(Layer):
    """E stacked gated experts, dropless. ``forward(x [.., H], ids [.., k],
    gates [.., k])`` -> [.., H]. ``tokens_per_expert`` (int32 [E], a buffer
    on the device) adds up how many rows each expert was given, call by
    call; nothing reads it back but whoever asks (``numpy()``)."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int):
        super().__init__()
        init = I.Normal(0.0, 0.02)
        self.w_gu = self.create_parameter(
            [num_experts, hidden_size, 2 * intermediate_size],
            default_initializer=init)
        self.w_dn = self.create_parameter(
            [num_experts, intermediate_size, hidden_size],
            default_initializer=init)
        self.register_buffer("tokens_per_expert", Tensor(
            jnp.zeros([num_experts], jnp.int32), _internal=True))

    def forward(self, x, ids, gates):
        lead, h = tuple(x.shape[:-1]), x.shape[-1]
        k = ids.shape[-1]

        def run(x, w_gu, w_dn, ids, gates):
            out, sizes = routed_experts(
                x.reshape(-1, h), w_gu, w_dn, ids.reshape(-1, k),
                gates.reshape(-1, k))
            return out.reshape(*lead, h), sizes

        out, sizes = apply(run, x, self.w_gu, self.w_dn, ids, gates,
                           op_name="routed_experts")
        self.tokens_per_expert.set_value(
            self.tokens_per_expert._data + sizes._data)
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
