"""What the training-path decoders share — ``zaya.py``, ``afmoe.py``,
``qwen3_next.py``, ``minicpm_sala.py``, ``granite_hybrid.py``,
``smallthinker.py`` — in ONE place: no decoder file imports another decoder's file, every one imports
this (``tests/test_layering.py``). A new family writes its mixers, its
block and its config, and DECLARES the rest:

* ``held_share``: what of the published model a config holds;
* ``DecoderStack``: embedding, the held layers, the last norm, and the
  loop over them. A family's ``XModel`` says how layer ``i`` is built,
  which norm class, which multiplier on the embedding;
* ``CausalLM``: ``loss``, ``stacked`` (one buffer over the layers of one
  kind) and the plain untied head; ``RoutedCausalLM`` adds the routed
  blocks' counters. Any other HEAD stays with its family, as its own
  ``forward``: tied, scaled before or after are different traced ops;
* ``SwiGLU`` and the two rotations, ``rope`` and ``partial_rope``.

Nothing here asks a family's name: a difference is a value the family
hands over or a method it overrides. **Training forward only: no cache,
no engine** — ``llama.py`` and ``gpt.py`` carry the engine's caches and
are no part of this (ROADMAP D6).
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import nn
from ..nn import functional as F


def held_share(config):
    """What of the published model a model built from ``config`` holds
    -> (the ids of its layers, the rows of its vocabulary). The share is
    the config's own fields where it has them (``first_layer`` /
    ``held_layers``, ``vocab_rows``, ``first_expert`` / ``held_experts``);
    one left None is filled in place with all of it (a config calls this
    from its ``__post_init__``), and a config without a field holds all."""
    c = config
    first = getattr(c, "first_layer", 0)
    whole = dict(held_layers=c.num_hidden_layers - first,
                 vocab_rows=c.vocab_size,
                 held_experts=getattr(c, "num_experts", None))
    for field, all_of_it in whole.items():
        if hasattr(c, field) and getattr(c, field) is None:
            setattr(c, field, all_of_it)
    layers = getattr(c, "held_layers", whole["held_layers"])
    return (list(range(first, first + layers)),
            getattr(c, "vocab_rows", c.vocab_size))


def rope(x, theta: float):
    """Half-split rotation of every dim of x [B, S, h, d] by its
    position's angle, in float32."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2].astype(jnp.float32), x[..., d // 2:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def partial_rope(x, theta: float, rot: int):
    """Half-split rotation of the first ``rot`` dims of x [B, S, h, d], in
    what ``x`` and float32 promote to. NOT ``rope`` with one more
    argument: where ``rot`` is the whole head this one still traces the
    empty rest into the concatenation, and it converts a 16-bit half once
    a product where ``rope`` converts it once — other ops in a cell's
    step, which is to stay the program it is (PR 43 compared the
    jaxprs)."""
    s = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


class SwiGLU(nn.Layer):
    """``(silu(m W1) * (m W3)) W2``, no bias."""

    def __init__(self, hidden_size: int, width: int):
        super().__init__()
        self.gate_proj = nn.Linear(hidden_size, width, bias_attr=False)
        self.up_proj = nn.Linear(hidden_size, width, bias_attr=False)
        self.down_proj = nn.Linear(width, hidden_size, bias_attr=False)

    def forward(self, m):
        return self.down_proj(F.silu(self.gate_proj(m)) * self.up_proj(m))


class DecoderStack(nn.Layer):
    """``embed_tokens``, the held ``layers`` (``layer_ids``: their
    published indices) and the last ``norm``. ``block(i)`` builds layer
    ``i``; ``norm`` is the class of the last norm; ``multiplier`` (or
    None) scales the embedding; ``routing`` says whether a block takes
    the list its discrete choices are appended to."""

    def __init__(self, config, block, norm=nn.RMSNorm, multiplier=None,
                 routing=True):
        super().__init__()
        c = self.config = config
        self.layer_ids, rows = held_share(c)
        self.embed_tokens = nn.Embedding(rows, c.hidden_size)
        self.layers = nn.LayerList([block(i) for i in self.layer_ids])
        self.norm = norm(c.hidden_size, c.rms_norm_eps)
        self.multiplier, self.hands_routing = multiplier, routing

    def forward(self, input_ids, routing=None):
        x = self.embed_tokens(input_ids)
        if self.multiplier is not None:
            x = x * self.multiplier
        for layer in self.layers:
            x = layer(x, routing) if self.hands_routing else layer(x)
        return self.norm(x)


class CausalLM(nn.Layer):
    """``model`` (a ``DecoderStack``) and, unless the head is ``tied``
    to the embedding, an ``lm_head`` over the same rows; a family with
    another head than the plain untied one writes its own ``forward``.
    Training forward only: there is no ``init_cache`` /
    ``forward_with_cache``, so no engine serves it."""

    def __init__(self, config, model: DecoderStack, tied=False):
        super().__init__()
        self.config, self.model = config, model
        if not tied:
            self.lm_head = nn.Linear(config.hidden_size,
                                     held_share(config)[1], bias_attr=False)

    def forward(self, input_ids, routing=None):
        """``routing``: a list that is given every block's discrete
        choice (a routed block's expert ids [B, S, k], a sparse block's
        table), in order: a train step may return them."""
        return self.lm_head(self.model(input_ids, routing))

    def loss(self, input_ids, labels):
        from ..tensor import manipulation as M

        logits = self(input_ids)
        b, s, v = logits.shape
        return F.cross_entropy(M.reshape(logits, [b * s, v]),
                               M.reshape(labels, [b * s]))

    @staticmethod
    def stacked(layers, buffer: str, absent=None):
        """[layers, ...] on the device: ``buffer`` of each layer, and
        ``absent(layer)`` for a layer that has no such buffer."""
        return jnp.stack([getattr(layer, buffer)._data
                          if buffer in layer._buffers else absent(layer)
                          for layer in layers])


class RoutedCausalLM(CausalLM):
    """The counters of the routed blocks: a block says ``routed`` and a
    routed one holds ``mlp.experts``, an ``nn.RoutedExperts``."""

    def routed_layers(self):
        return [layer for layer in self.model.layers if layer.routed]

    def _experts(self):
        return [layer.mlp.experts for layer in self.routed_layers()]

    def tokens_per_expert(self):
        """[routed blocks, held experts] int32 on the device: rows each
        held expert has been given since the model was built."""
        return self.stacked(self._experts(), "tokens_per_expert")

    def pairs_routed(self):
        """[routed blocks] int32: every (token, choice) pair a block saw,
        whichever expert it went to (where every expert is held, the
        rows they got add up to them)."""
        return self.stacked(self._experts(), "pairs_routed",
                            lambda e: jnp.sum(e.tokens_per_expert._data))

    def calls_in_full(self):
        """[routed blocks] int32: the calls in which a block that holds a
        share was sent more pairs than its bound on the rows it computes
        at a time (``nn.RoutedExperts``); where every expert is held
        there is no bound to pass: zeros."""
        return self.stacked(self._experts(), "calls_in_full",
                            lambda e: jnp.zeros([], jnp.int32))
