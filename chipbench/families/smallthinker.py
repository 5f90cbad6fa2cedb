"""Family ``smallthinker``: SmallThinker decoders (PowerInfer; a published
``config.json`` of ``model_name`` smallthinker_*), run through the
program's ``SmallThinkerForCausalLM`` on the TRAINING path. The serving
functions raise ``NotImplementedError``: the program has no cache for
this family.

All the benchmark knows of how this family meets the program, as
``families/qwen3next.py`` (every leaf is trained, and nothing stands in
for a trained router's balance: the configuration says what was read):
which parameter of the program is which weight of the reference, how the
train step is built, how the seeded weights get their scales, how the
plain reference (``smallthinker_reference.py``) is given the same weights
and the same SHARE. What is run is under ``held``: ``layers`` blocks from
the published layer ``first_layer`` on (each of the kind the published
``sliding_window_layout`` and ``rope_layout`` give it), ``experts`` of the
published ``moe_num_primary_experts`` from ``first_expert`` on,
``vocab_rows`` rows of embedding and head. ``training.recompute`` is what
a block recomputes in its backward pass.

A routed block's choice is a SET of ``top_k`` experts a token, made from
the block's INPUT. The step returns them best first;
``Trainer.routing()`` packs a set into one integer (``afmoe.pack``: six
ids of six bits), so that job kind ``train_routed`` serves unchanged and
its ``route_flip_share.mean`` is the share of (token, block) pairs whose
chosen SET differs.
"""
from __future__ import annotations

import gc
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import shapes_smallthinker, weights
from ..schedule import seed_words
from . import afmoe_reference, gpt_reference, smallthinker_reference, zaya
from .afmoe import (  # noqa: F401 (the serving refusals are the family's too)
    Server, control_gaps, kv_bytes_per_token, moe_counters, pack,
    served_gaps, unpack)
from .gpt2 import _difference_norm
from .zaya import _MIN_ROUNDINGS, _leaf, _leaf_jit, _scale


def sizes(cfg: Dict) -> Dict:
    """The sizes as run, under the names ``afmoe.sizes`` uses where the
    thing is the same. ``layer_kinds``: for each held block its sliding
    window (None: full attention) and whether q and k are rotated."""
    held = cfg["held"]
    first = held["first_layer"]
    kinds = [(cfg["sliding_window_size"]
              if cfg["sliding_window_layout"][i] else None,
              bool(cfg["rope_layout"][i]))
             for i in range(first, first + held["layers"])]
    return dict(
        hidden=cfg["hidden_size"], layers=held["layers"], first_layer=first,
        layer_kinds=kinds, q_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        heads=cfg["num_attention_heads"], window=cfg["sliding_window_size"],
        experts=held["experts"], first_expert=held["first_expert"],
        published_experts=cfg["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"],
        expert_inner=cfg["moe_ffn_hidden_size"],
        theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        vocab=held["vocab_rows"], vocab_rows=held["vocab_rows"],
        recompute=cfg["training"]["recompute"])


def _block_leaves(z: Dict):
    """(group suffix, reference name, program name, shape, kind, name its
    scale goes by) of one block; the two stacked expert matrices are
    groups of their own, as ``afmoe._block_leaves``."""
    h, d = z["hidden"], z["head_dim"]
    q, k = z["q_heads"] * d, z["kv_heads"] * d
    e, f = z["experts"], z["expert_inner"]
    small = [
        ("norm_in.g", "input_layernorm.weight", (h,), "ones"),
        ("wq", "self_attn.q_proj.weight", (h, q), "normal"),
        ("wk", "self_attn.k_proj.weight", (h, k), "normal"),
        ("wv", "self_attn.v_proj.weight", (h, k), "normal"),
        ("wo", "self_attn.o_proj.weight", (q, h), "normal"),
        ("norm_post.g", "post_attention_layernorm.weight", (h,), "ones"),
        ("router.w", "mlp.router.weight", (h, z["published_experts"]),
         "normal"),
    ]
    return ([("", ref, prog, shape, kind, ref)
             for ref, prog, shape, kind in small]
            + [(".gu", "w", "mlp.experts.w_gu", (e, h, 2 * f), "normal",
                "experts.w_gu"),
               (".dn", "w", "mlp.experts.w_dn", (e, f, h), "normal",
                "experts.w_dn")])


def _all_leaves(cfg: Dict):
    """(group, reference name, program name, shape, kind, scale's name)
    of every seeded array, in the fixed order that numbers them: the
    parameters AdamW holds, all of them. Blocks are numbered from 0 as
    held."""
    z = sizes(cfg)
    out = [("embed", "wte", "model.embed_tokens.weight",
            (z["vocab_rows"], z["hidden"]), "normal", "wte")]
    for n in range(z["layers"]):
        out += [(f"h.{n}{suffix}", ref, f"model.layers.{n}.{prog}", shape,
                 kind, scaled)
                for suffix, ref, prog, shape, kind, scaled
                in _block_leaves(z)]
    out += [("head", "norm_f.g", "model.norm.weight", (z["hidden"],), "ones",
             "norm_f.g"),
            ("head", "lm_head", "lm_head.weight",
             (z["hidden"], z["vocab_rows"]), "normal", "lm_head")]
    return out


leaves = _all_leaves


def matrix_leaves(cfg: Dict) -> List[str]:
    """The matrices whose change after two steps is held to its
    expectation: ``zaya.matrix_leaves``' rule on this family's leaves
    (every matrix at the cell's size, the embedding among them)."""
    o = cfg["optimizer"]
    noisy = (o["stochastic_rounding"] and not o["master_weights"]
             and cfg["dtype"] == "bfloat16")
    out = []
    for g, ref, _, shape, _, scaled in leaves(cfg):
        if len(shape) < 2:
            continue
        std = weights.INIT_STD * _scale(cfg, scaled)
        jumps = int(np.prod(shape)) * o["lr"] / (2.0 ** -8 * std)
        if not noisy or jumps >= _MIN_ROUNDINGS:
            out.append(f"{g}/{ref}")
    return out


def total_params(cfg: Dict) -> int:
    """Parameters as run."""
    return sum(int(np.prod(l[3])) for l in _all_leaves(cfg))


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    return shapes_smallthinker.train_flops_per_token(sizes(cfg), seq)


# -- seeded weights ----------------------------------------------------------


def _statics(cfg: Dict):
    dtype = jnp.dtype(cfg["dtype"])
    return [(tuple(shape), kind, _scale(cfg, scaled), dtype)
            for _, _, _, shape, kind, scaled in _all_leaves(cfg)]


def make_all(cfg: Dict, seed: int) -> List[jax.Array]:
    """Every seeded array in ONE jitted call, in the stored type."""
    lo, hi = seed_words(seed)
    statics = _statics(cfg)
    return jax.jit(lambda lo, hi: [_leaf(lo, hi, i, *st)
                                   for i, st in enumerate(statics)])(
        jnp.uint32(lo), jnp.uint32(hi))


def make_leaf(cfg: Dict, seed: int, index: int) -> jax.Array:
    """Array ``index`` alone (bit-equal to ``make_all``'s)."""
    lo, hi = seed_words(seed)
    return _leaf_jit(jnp.uint32(lo), jnp.uint32(hi), jnp.uint32(index),
                     *_statics(cfg)[index])


# -- the plain reference, on the same seeded weights and the same share ------


def reference(cfg: Dict, seed: int, precision: str = "f32", follow=None):
    """The plain reference on the seeded weights, as ``afmoe.reference``:
    ``follow`` is, for each training forward it will make, the [blocks,
    B, S] packed sets the program's tokens met."""
    z = sizes(cfg)
    groups: Dict[str, List[Tuple[int, str]]] = {}
    for i, leaf in enumerate(_all_leaves(cfg)):
        groups.setdefault(leaf[0], []).append((i, leaf[1]))
    if follow is not None:
        follow = [unpack(f, z["published_experts"], z["top_k"])
                  for f in follow]

    def made(group):
        return {ref: make_leaf(cfg, seed, i).astype(jnp.float32)
                for i, ref in groups[group]}

    return smallthinker_reference.Reference(
        made,
        layers=[smallthinker_reference.Layer(w, p)
                for w, p in z["layer_kinds"]],
        nq=z["q_heads"], nkv=z["kv_heads"], d=z["head_dim"],
        theta=z["theta"], eps=z["eps"], top_k=z["top_k"],
        first_expert=z["first_expert"], follow=follow, precision=precision)


@jax.jit
def _rounded_change_norms(p0, p1, p2):
    """``gpt_reference._change_norms`` for bfloat16 parameters written
    back with stochastic rounding, with the value the first write stores
    made by ``lax.reduce_precision``. The accepted one rounds ``p1`` by
    ``astype(bfloat16).astype(float32)``, and on the TPU that pair is
    elided inside the fusion (``xla_allow_excess_precision``): the
    second write's variance is then read at ``p1 + u2`` for ``stored +
    u2``, hardly another number where a step's update ``u`` is over a
    bfloat16 spacing and 19% too much of the whole where it is 0.013 of
    one, as this family's embedding's (PERF.md section 2). No compiler
    may drop a ``reduce_precision``."""
    out = {}
    for k in p2:
        stored = jax.lax.reduce_precision(p1[k], exponent_bits=8,
                                          mantissa_bits=7)
        out[k] = jnp.sqrt(
            jnp.sum(jnp.square(p2[k] - p0[k]))
            + gpt_reference._rounding_variance(p1[k])
            + gpt_reference._rounding_variance(stored + (p2[k] - p1[k])))
    return out


def _train_two_steps(ref, batches, hyper: Dict) -> Dict:
    """``gpt_reference.train_two_steps`` for this family's storage
    (bfloat16, stochastic rounding, no master weights), the expectation
    of a parameter's change by ``_rounded_change_norms``: the same two
    passes, the same order, the first gradient waiting on the host."""
    hyper = {k: hyper[k] for k in ("lr", "beta1", "beta2", "eps",
                                   "weight_decay")}
    adamw, norms = gpt_reference._adamw, gpt_reference._norms
    zeros = lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree)
    first: Dict = {}
    out = {"loss": [], "grad_norm": {}, "delta_norm": {}, "moment_norm": {}}
    for group, g in ref.loss_and_grads(ref.get, *batches[0]):
        first[group] = jax.device_get(g)
        for k, n in norms(g).items():
            out["grad_norm"][f"{group}/{k}"] = n
    out["loss"].append(ref.loss)

    def after_one(group):
        p0 = ref.get(group)
        return adamw(p0, zeros(p0), zeros(p0), first[group], step=1, **hyper)

    for group, g in ref.loss_and_grads(lambda grp: after_one(grp)[0],
                                       *batches[1]):
        p1, m1, v1 = after_one(group)
        p2, m2, v2 = adamw(p1, m1, v1, g, step=2, **hyper)
        for k, n in _rounded_change_norms(ref.get(group), p1, p2).items():
            out["delta_norm"][f"{group}/{k}"] = float(n)
        for tag, tree in (("m", m2), ("v", v2)):
            for k, n in norms(tree).items():
                out["moment_norm"][f"{tag}/{group}/{k}"] = n
        del first[group]
    out["loss"].append(ref.loss)
    return out


def reference_training(cfg: Dict, seed: int, batches, precision="f32",
                       follow=None) -> Dict:
    """The two AdamW steps the ``train_routed`` job follows, and the
    reference's OWN routing of the first step's forward (``routing``:
    [blocks] of (packed set [B, S], mean chosen probability))."""
    o = cfg["optimizer"]
    if not (o["stochastic_rounding"] and cfg["dtype"] == "bfloat16"
            and not o["master_weights"]):
        raise NotImplementedError("storage other than bfloat16 written "
                                  "back with stochastic rounding")
    ref = reference(cfg, seed, precision, follow)
    out = _train_two_steps(ref, batches, o)
    experts = sizes(cfg)["published_experts"]
    stats = afmoe_reference.routing_stats(ref.first_routing, experts)
    out["routing"] = [(pack(e, experts), top) for e, top in ref.first_routing]
    print(f"[chipbench] smallthinker reference ({precision}) routing of "
          "step 1: " + " ".join(f"{k}={v:.4f}" for k, v in stats.items()),
          flush=True)
    return out


# -- the program -------------------------------------------------------------


def _build_model(cfg: Dict, seed: int):
    """``SmallThinkerForCausalLM`` at the published widths holding the
    seeded weights of its share, as ``afmoe._build_model``. Returns the
    model and its parameters in ``_all_leaves`` order."""
    import paddle_tpu as paddle
    from paddle_tpu.models import SmallThinkerConfig, SmallThinkerForCausalLM
    from paddle_tpu.nn.layer import layers as _layers

    z = sizes(cfg)
    paddle.seed(seed % (1 << 31))
    with paddle.LazyGuard():
        model = SmallThinkerForCausalLM(SmallThinkerConfig(
            vocab_size=z["vocab_rows"], hidden_size=z["hidden"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=z["q_heads"],
            num_key_value_heads=z["kv_heads"], head_dim=z["head_dim"],
            moe_ffn_hidden_size=z["expert_inner"],
            moe_num_primary_experts=z["published_experts"],
            moe_num_active_primary_experts=z["top_k"],
            moe_primary_router_apply_softmax=cfg[
                "moe_primary_router_apply_softmax"],
            norm_topk_prob=cfg["norm_topk_prob"],
            rope_layout=cfg["rope_layout"],
            sliding_window_layout=cfg["sliding_window_layout"],
            sliding_window_size=cfg["sliding_window_size"],
            rope_theta=z["theta"], rms_norm_eps=z["eps"],
            first_layer=z["first_layer"], held_layers=z["layers"],
            first_expert=z["first_expert"], held_experts=z["experts"],
            recompute=z["recompute"]))
    named = dict(model.named_parameters())
    want = _all_leaves(cfg)
    if sorted(named) != sorted(l[2] for l in want):
        raise RuntimeError(
            "the program's parameters are not the family's leaves: "
            f"{sorted(set(named) ^ {l[2] for l in want})[:6]}")
    model._cast_params(cfg["dtype"])
    params = []
    for (_, _, prog, shape, _, _), arr in zip(want, make_all(cfg, seed)):
        p = named[prog]
        lazy = getattr(p, "_lazy_init", None)
        if lazy is None or tuple(lazy[1]) != tuple(shape):
            raise RuntimeError(f"{prog}: shape {lazy and lazy[1]} is not "
                               f"the published {shape}")
        p._data, p._lazy_init = arr, None
        _layers._lazy_init_state["pending"] -= 1
        params.append(p)
    return model, params


def _live_share(m, w_gu, chosen, first: int, act):
    """Of the hidden units of the (token, held expert) pairs of one
    block, the share ``act`` leaves other than zero: a held expert at a
    time over every token, counted where the token chose it."""
    m = m.reshape(-1, m.shape[-1])
    chosen = chosen.reshape(-1, chosen.shape[-1])
    f = w_gu.shape[-1] // 2

    def expert(counted, e):
        met = jnp.any(chosen == first + e, axis=-1)
        live = (act(m @ w_gu[e], f) != 0) & met[:, None]
        return (counted[0] + jnp.sum(live, dtype=jnp.int32),
                counted[1] + jnp.sum(met, dtype=jnp.int32) * f), None

    zero = jnp.zeros([], jnp.int32)
    (live, units), _ = jax.lax.scan(expert, (zero, zero),
                                    jnp.arange(w_gu.shape[0]))
    return live / jnp.maximum(units, 1)


class Trainer(zaya.Trainer):
    """The compiled train step with its state, as ``qwen3next.Trainer``:
    ``jit.to_static`` over the model and AdamW. The step returns, beside
    its loss, the set of experts every token chose in every block, best
    first. ``free()`` reads the experts' counters off the device ONCE and
    records them into ``obs.ring()`` as ``moe.tokens_per_expert``
    (``counts`` [blocks, held]), ``moe.pairs_routed`` (``pairs``
    [blocks]), ``moe.calls_in_full`` (``calls`` [blocks]),
    ``moe.rows_a_window`` (``rows`` [blocks]: what one pass of a block's
    traced call ran over, the layer's own record) and
    ``moe.act_live_share`` (``shares`` [blocks]: one forward of the LAST
    batch through the trained model's own halves, after the window and
    off the timed step)."""

    def __init__(self, cfg: Dict, seed: int):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as popt
        from paddle_tpu.tensor import manipulation as M

        self._paddle = paddle
        self.cfg, self.seed = cfg, seed
        self._z = sizes(cfg)
        model, self.params = _build_model(cfg, seed)
        self._held = [(i, f"{leaf[0]}/{leaf[1]}", p) for i, (leaf, p)
                      in enumerate(zip(_all_leaves(cfg), self.params))]
        o = cfg["optimizer"]
        opt = popt.AdamW(
            learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
            epsilon=o["eps"], weight_decay=o["weight_decay"],
            parameters=self.params,
            multi_precision=o["master_weights"],
            use_stochastic_rounding=o["stochastic_rounding"],
            moment_dtype=o["moment_dtype"])

        def step(ids, labels):
            chosen = []
            logits = model(ids, chosen)
            b, s, v = logits.shape
            loss = F.cross_entropy(
                M.reshape(logits, [b * s, v]), M.reshape(labels, [b * s]))
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss, M.concat(chosen, axis=-1)       # [B, S, blocks*k]

        self.model, self.opt, self._chosen, self._ids = model, opt, None, None
        self._step = paddle.jit.to_static(
            step, layers=[model], optimizers=[opt])

    def routing(self) -> np.ndarray:
        """[blocks, B, S] int64: the SET every token chose in the LAST
        step, packed (one device-to-host read)."""
        ids = np.asarray(self._chosen._data)
        k = self._z["top_k"]
        ids = ids.reshape(*ids.shape[:-1], ids.shape[-1] // k, k)
        return np.moveaxis(pack(ids, self._z["published_experts"]), -1, 0)

    def step(self, ids, labels) -> float:
        self._ids = ids
        return super().step(ids, labels)

    def delta_norms(self) -> Dict[str, float]:
        return self._by_leaf(
            [_difference_norm(p._data, make_leaf(self.cfg, self.seed, i))
             for i, _, p in self._held])

    def act_live_shares(self) -> List[float]:
        """Per block, ``_live_share`` on the batch of the last step: the
        block's own router, attention half and MLP half, the experts'
        declared activation, nothing differentiated. Compiled as one
        function with the ring's recording off: the program-span readers
        take the ring's NEWEST ``to_static.call`` for the trainer's
        step."""
        from paddle_tpu import obs
        from paddle_tpu.base.tape import apply
        from paddle_tpu.tensor import manipulation as M

        paddle, stack = self._paddle, self.model.model

        def forward(ids):
            x, shares = stack.embed_tokens(ids), []
            for block in stack.layers:
                e = block.mlp.experts
                chosen, gates = block.mlp.router(x)
                h = block._attn_half(x)
                shares.append(apply(
                    lambda m, w, c: _live_share(m, w, c, e.first,
                                                e.activation),
                    block.post_attention_layernorm(h), e.w_gu, chosen,
                    op_name="live_share"))
                x = block._mlp_half(h, chosen, gates)[0]
            return M.stack(shares)

        was = obs.set_enabled(False)
        try:
            with paddle.no_grad():
                shares = paddle.jit.to_static(forward, layers=[self.model])(
                    paddle.to_tensor(self._ids))
        finally:
            obs.set_enabled(was)
        return np.asarray(shares._data, np.float64).tolist()

    def free(self) -> None:
        from paddle_tpu import obs

        m = self.model
        obs.instant("moe.tokens_per_expert",
                    counts=np.asarray(m.tokens_per_expert()).tolist())
        obs.instant("moe.pairs_routed",
                    pairs=np.asarray(m.pairs_routed()).tolist())
        obs.instant("moe.calls_in_full",
                    calls=np.asarray(m.calls_in_full()).tolist())
        obs.instant("moe.rows_a_window",
                    rows=[getattr(e, "rows_a_window", None)
                          for e in m._experts()])
        if self._ids is not None:
            obs.instant("moe.act_live_share", shares=self.act_live_shares())
        self.model = self.opt = self._step = self.params = None
        jax.clear_caches()
        gc.collect()
