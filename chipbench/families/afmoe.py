"""Family ``afmoe``: AFMoE decoders (Arcee Trinity; a published
``config.json`` of ``model_type`` afmoe), run through the program's
``AfmoeForCausalLM`` on the TRAINING path. The serving functions raise
``NotImplementedError``: the program has no cache for this family.

All the benchmark knows of how this family meets the program, as
``families/zaya.py``: which parameter of the program is which weight of
the reference, how the train step is built, how the seeded weights get
their scales, how the plain reference (``afmoe_reference.py``) is given
the same weights and the same SHARE. What is run is under ``held``:
``layers`` blocks from the published layer ``first_layer`` on (each of
the kind the published ``layer_types`` and ``num_dense_layers`` give
it), ``experts`` of the published ``num_experts`` from ``first_expert``
on, ``vocab_rows`` rows of embedding and head. ``training.recompute`` is
what a block recomputes in its backward pass.

``frozen`` and ``router_balancing.rate`` are ``zaya.py``'s: weights kept
out of AdamW, and the BENCHMARK's stand-in for a trained router's
balance — after each step's routing the train step built here moves the
HELD experts' selection bias by ``rebalanced`` below, against the even
share of all the published experts; the program's layers know no rule.

A routed block's choice is a SET of ``top_k`` experts a token. The step
returns them best first; ``Trainer.routing()`` packs a set into one
integer (``pack``, which sorts), so that job kind ``train_routed``
serves unchanged and its ``route_flip_share.mean`` is the share of
(token, routed block) pairs whose chosen SET differs.
"""
from __future__ import annotations

import gc
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import shapes_afmoe, weights
from ..schedule import seed_words
from . import afmoe_reference, gpt_reference, zaya
from .gpt2 import _difference_norm
from .zaya import (  # noqa: F401 (the serving refusals are the family's too)
    _MIN_ROUNDINGS, Server, _leaf, _leaf_jit, _scale, control_gaps,
    kv_bytes_per_token, served_gaps)


def sizes(cfg: Dict) -> Dict:
    """The sizes as run. ``layer_kinds``: for each held block its sliding
    window (None: full attention) and whether its MLP is routed."""
    held = cfg["held"]
    first = held["first_layer"]
    kinds = [(cfg["sliding_window"]
              if cfg["layer_types"][i] == "sliding_attention" else None,
              i >= cfg["num_dense_layers"])
             for i in range(first, first + held["layers"])]
    return dict(
        hidden=cfg["hidden_size"], layers=held["layers"], first_layer=first,
        layer_kinds=kinds, q_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        heads=cfg["num_attention_heads"], window=cfg["sliding_window"],
        experts=held["experts"], first_expert=held["first_expert"],
        published_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        expert_inner=cfg["moe_intermediate_size"],
        shared_inner=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        dense_inner=cfg["intermediate_size"],
        theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        route_scale=float(cfg["route_scale"]),
        embed_scale=(math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"]
                     else 1.0),
        vocab=held["vocab_rows"], vocab_rows=held["vocab_rows"],
        recompute=cfg["training"]["recompute"],
        balance_rate=float(cfg.get("router_balancing", {}).get("rate", 0)))


def _block_leaves(z: Dict, routed: bool):
    """(group suffix, reference name, program name, shape, kind, name its
    scale goes by) of one block; a routed block's two stacked expert
    matrices are groups of their own, as ``zaya._block_leaves``."""
    h, d = z["hidden"], z["head_dim"]
    q, k = z["q_heads"] * d, z["kv_heads"] * d
    small = [
        ("norm_in.g", "input_layernorm.weight", (h,), "ones"),
        ("wq", "self_attn.q_proj.weight", (h, q), "normal"),
        ("wk", "self_attn.k_proj.weight", (h, k), "normal"),
        ("wv", "self_attn.v_proj.weight", (h, k), "normal"),
        ("wg", "self_attn.gate_proj.weight", (h, q), "normal"),
        ("wo", "self_attn.o_proj.weight", (q, h), "normal"),
        ("q_norm.g", "self_attn.q_norm.weight", (d,), "ones"),
        ("k_norm.g", "self_attn.k_norm.weight", (d,), "ones"),
        ("norm_post_attn.g", "post_attention_layernorm.weight", (h,), "ones"),
        ("norm_pre_mlp.g", "pre_mlp_layernorm.weight", (h,), "ones"),
        ("norm_post_mlp.g", "post_mlp_layernorm.weight", (h,), "ones"),
    ]
    if not routed:
        i = z["dense_inner"]
        small += [("w1", "mlp.gate_proj.weight", (h, i), "normal"),
                  ("w3", "mlp.up_proj.weight", (h, i), "normal"),
                  ("w2", "mlp.down_proj.weight", (i, h), "normal")]
        return [("", ref, prog, shape, kind, ref)
                for ref, prog, shape, kind in small]
    e, f, s = z["experts"], z["expert_inner"], z["shared_inner"]
    small += [
        ("router.w", "mlp.router.weight", (h, z["published_experts"]),
         "normal"),
        # a buffer: seeded like a weight, held by no optimizer
        ("router.bias", "mlp.router.bias", (z["published_experts"],),
         "buffer"),
        ("shared.w1", "mlp.shared_experts.gate_proj.weight", (h, s), "normal"),
        ("shared.w3", "mlp.shared_experts.up_proj.weight", (h, s), "normal"),
        ("shared.w2", "mlp.shared_experts.down_proj.weight", (s, h),
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
    of every seeded array, buffers included, in the fixed order that
    numbers them. Blocks are numbered from 0 as held."""
    z = sizes(cfg)
    out = [("embed", "wte", "model.embed_tokens.weight",
            (z["vocab_rows"], z["hidden"]), "normal", "wte")]
    for n, (_, routed) in enumerate(z["layer_kinds"]):
        out += [(f"h.{n}{suffix}", ref, f"model.layers.{n}.{prog}", shape,
                 kind, scaled)
                for suffix, ref, prog, shape, kind, scaled
                in _block_leaves(z, routed)]
    out += [("head", "norm_f.g", "model.norm.weight", (z["hidden"],), "ones",
             "norm_f.g"),
            ("head", "lm_head", "lm_head.weight",
             (z["hidden"], z["vocab_rows"]), "normal", "lm_head")]
    return out


def _trained(cfg: Dict, leaf) -> bool:
    return leaf[4] != "buffer" and not any(
        leaf[1].startswith(prefix) for prefix in cfg.get("frozen", []))


def leaves(cfg: Dict) -> List[Tuple[str, str, str, Tuple[int, ...], str]]:
    """The PARAMETERS AdamW holds, as ``gpt2.leaves``."""
    return [l for l in _all_leaves(cfg) if _trained(cfg, l)]


def matrix_leaves(cfg: Dict) -> List[str]:
    """The matrices whose change after two steps is held to its
    expectation: ``zaya.matrix_leaves``' rule on this family's leaves."""
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
    """Parameters as run (the routers' bias buffers are not among them)."""
    return sum(int(np.prod(l[3])) for l in _all_leaves(cfg)
               if l[4] != "buffer")


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    return shapes_afmoe.train_flops_per_token(sizes(cfg), seq)


# -- seeded weights ----------------------------------------------------------


def _statics(cfg: Dict):
    dtype = jnp.dtype(cfg["dtype"])
    return [(tuple(shape), kind, _scale(cfg, scaled),
             jnp.dtype(jnp.float32) if kind == "buffer" else dtype)
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


# -- a token's set of experts as one integer ---------------------------------


def _bits(experts: int) -> int:
    return max(1, (experts - 1).bit_length())


def pack(ids, experts: int) -> np.ndarray:
    """ids [..., k] (any order) -> [...] int64: the SET as one integer."""
    ids = np.sort(np.asarray(ids, np.int64), axis=-1)
    shifts = _bits(experts) * np.arange(ids.shape[-1], dtype=np.int64)
    return np.sum(ids << shifts, axis=-1)


def unpack(codes, experts: int, k: int) -> np.ndarray:
    """``pack``'s inverse: [...] -> [..., k] int32, ascending."""
    bits = _bits(experts)
    codes = np.asarray(codes, np.int64)[..., None]
    return ((codes >> (bits * np.arange(k, dtype=np.int64)))
            & ((1 << bits) - 1)).astype(np.int32)


# -- the plain reference, on the same seeded weights and the same share ------


def rebalanced(bias, ids, rate: float, first: int, held: int):
    """The balancing rule on the HELD experts: ``bias`` [E] float32 after
    one step whose (token, choice) pairs met the experts ``ids`` (any
    shape, int): for e held, ``bias_e += rate * clip(1 - load_e / even
    share of all E, -1, 1)``."""
    experts = bias.shape[0]
    load = jnp.bincount(ids.reshape(-1), length=experts).astype(jnp.float32)
    move = rate * jnp.clip(1.0 - load * (experts / ids.size), -1.0, 1.0)
    e = jnp.arange(experts)
    return bias + jnp.where((e >= first) & (e < first + held), move, 0.0)


def reference(cfg: Dict, seed: int, precision: str = "f32", follow=None):
    """The plain reference on the seeded weights. ``follow``: for each
    training forward it will make, the [routed blocks, B, S] packed sets
    the program's tokens met; the selection bias of forward n is then the
    seeded one moved by the balancing rule along the n routings before."""
    z = sizes(cfg)
    trained: Dict[str, List[Tuple[int, str]]] = {}
    untrained: Dict[str, List[Tuple[int, str]]] = {}
    for i, leaf in enumerate(_all_leaves(cfg)):
        (trained if _trained(cfg, leaf) else untrained).setdefault(
            leaf[0], []).append((i, leaf[1]))
    if follow is not None:
        follow = [unpack(f, z["published_experts"], z["top_k"])
                  for f in follow]
    routed_index = np.cumsum([r for _, r in z["layer_kinds"]]) - 1

    def made(entries):
        return {ref: make_leaf(cfg, seed, i).astype(jnp.float32)
                for i, ref in entries}

    def fixed(block, step):
        out = made(untrained.get(f"h.{block}", []))
        if z["layer_kinds"][block][1]:
            for before in (follow or [])[:step]:
                out["router.bias"] = rebalanced(
                    out["router.bias"],
                    jnp.asarray(before[routed_index[block]]),
                    z["balance_rate"], z["first_expert"], z["experts"])
        return out

    return afmoe_reference.Reference(
        lambda group: made(trained[group]), fixed,
        layers=[afmoe_reference.Layer(w, r) for w, r in z["layer_kinds"]],
        nq=z["q_heads"], nkv=z["kv_heads"], d=z["head_dim"],
        theta=z["theta"], eps=z["eps"], top_k=z["top_k"],
        scale=z["route_scale"], first_expert=z["first_expert"],
        embed_scale=z["embed_scale"], follow=follow, precision=precision)


def reference_training(cfg: Dict, seed: int, batches, precision="f32",
                       follow=None) -> Dict:
    """The two AdamW steps the ``train_routed`` job follows, and the
    reference's OWN routing of the first step's forward (``routing``:
    [routed blocks] of (packed set [B, S], mean chosen score))."""
    ref = reference(cfg, seed, precision, follow)
    out = gpt_reference.train_two_steps(
        ref, batches, cfg["optimizer"], cfg["dtype"])
    z = sizes(cfg)
    stats = afmoe_reference.routing_stats(ref.first_routing,
                                          z["published_experts"])
    out["routing"] = [(pack(e, z["published_experts"]), top)
                      for e, top in ref.first_routing]
    print(f"[chipbench] afmoe reference ({precision}) routing of step 1: "
          + " ".join(f"{k}={v:.4f}" for k, v in stats.items()), flush=True)
    return out


def moe_counters():
    """(held rows [routed blocks, held], pairs routed [routed blocks]) as
    ``Trainer.free()`` recorded them last into ``obs.ring()``, or None
    where nothing was recorded."""
    from paddle_tpu import obs

    found = {}
    for e in obs.ring().dump():
        if e.get("name") in ("moe.tokens_per_expert", "moe.pairs_routed"):
            found[e["name"]] = e["args"]
    if len(found) < 2:
        return None
    return (found["moe.tokens_per_expert"]["counts"],
            found["moe.pairs_routed"]["pairs"])


# -- the program -------------------------------------------------------------


def _build_model(cfg: Dict, seed: int):
    """``AfmoeForCausalLM`` at the published widths holding the seeded
    weights of its share, as ``zaya._build_model``. Returns the model and
    its parameters (the ``frozen`` ones too) in ``_all_leaves`` order."""
    import paddle_tpu as paddle
    from paddle_tpu.models import AfmoeConfig, AfmoeForCausalLM
    from paddle_tpu.nn.layer import layers as _layers

    z = sizes(cfg)
    paddle.seed(seed % (1 << 31))
    with paddle.LazyGuard():
        model = AfmoeForCausalLM(AfmoeConfig(
            vocab_size=z["vocab_rows"], hidden_size=z["hidden"],
            intermediate_size=z["dense_inner"],
            moe_intermediate_size=z["expert_inner"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_dense_layers=cfg["num_dense_layers"],
            num_attention_heads=z["q_heads"],
            num_key_value_heads=z["kv_heads"], head_dim=z["head_dim"],
            layer_types=cfg["layer_types"],
            sliding_window=cfg["sliding_window"], rope_theta=z["theta"],
            rms_norm_eps=z["eps"], num_experts=z["published_experts"],
            num_experts_per_tok=z["top_k"],
            num_shared_experts=cfg["num_shared_experts"],
            route_norm=cfg["route_norm"], route_scale=z["route_scale"],
            mup_enabled=cfg["mup_enabled"], first_layer=z["first_layer"],
            held_layers=z["layers"], first_expert=z["first_expert"],
            held_experts=z["experts"], recompute=z["recompute"]))
    named = dict(model.named_parameters())
    buffers = dict(model.named_buffers())
    want = _all_leaves(cfg)
    if sorted(named) != sorted(l[2] for l in want if l[4] != "buffer"):
        raise RuntimeError(
            "the program's parameters are not the family's leaves: "
            f"{sorted(set(named) ^ {l[2] for l in want if l[4] != 'buffer'})[:6]}")
    model._cast_params(cfg["dtype"])
    params = []
    for (_, _, prog, shape, kind, _), arr in zip(want, make_all(cfg, seed)):
        if kind == "buffer":
            if tuple(buffers[prog].shape) != tuple(shape):
                raise RuntimeError(f"{prog}: buffer shape "
                                   f"{buffers[prog].shape} is not {shape}")
            buffers[prog]._data = arr
            continue
        p = named[prog]
        lazy = getattr(p, "_lazy_init", None)
        if lazy is None or tuple(lazy[1]) != tuple(shape):
            raise RuntimeError(f"{prog}: shape {lazy and lazy[1]} is not "
                               f"the published {shape}")
        p._data, p._lazy_init = arr, None
        _layers._lazy_init_state["pending"] -= 1
        params.append(p)
    return model, params


class Trainer(zaya.Trainer):
    """The compiled train step with its state, as ``zaya.Trainer`` (whose
    readers of the optimizer's state it keeps): ``jit.to_static`` over the
    model and AdamW, then the balancing rule on the held experts' bias.
    The step returns, beside its loss, the set of experts every token
    chose in every routed block, best first. ``free()`` reads the
    experts' counters off the device ONCE and records them into
    ``obs.ring()`` as ``moe.tokens_per_expert`` (``counts`` [routed
    blocks, held]) and ``moe.pairs_routed`` (``pairs`` [routed blocks])."""

    def __init__(self, cfg: Dict, seed: int):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as popt
        from paddle_tpu.tensor import manipulation as M

        self._paddle = paddle
        self.cfg, self.seed = cfg, seed
        self._z = z = sizes(cfg)
        model, every = _build_model(cfg, seed)
        self._held = []
        seeded = [(i, l) for i, l in enumerate(_all_leaves(cfg))
                  if l[4] != "buffer"]
        for p, (i, leaf) in zip(every, seeded):
            if _trained(cfg, leaf):
                self._held.append((i, f"{leaf[0]}/{leaf[1]}", p))
            else:
                p.stop_gradient = True
        self.params = [p for _, _, p in self._held]
        o, rate = cfg["optimizer"], z["balance_rate"]
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
            if rate:        # the benchmark's balancing rule, module docstring
                for layer, ids in zip(model.routed_layers(), chosen):
                    bias = layer.mlp.router.bias
                    bias.set_value(rebalanced(
                        bias._data, ids._data, rate, z["first_expert"],
                        z["experts"]))
            return loss, M.concat(chosen, axis=-1)  # [B, S, routed blocks*k]

        self.model, self.opt, self._chosen = model, opt, None
        self._step = paddle.jit.to_static(
            step, layers=[model], optimizers=[opt])

    def routing(self) -> np.ndarray:
        """[routed blocks, B, S] int64: the SET every token chose in the
        LAST step, packed (one device-to-host read)."""
        ids = np.asarray(self._chosen._data)
        k = self._z["top_k"]
        ids = ids.reshape(*ids.shape[:-1], ids.shape[-1] // k, k)
        return np.moveaxis(pack(ids, self._z["published_experts"]), -1, 0)

    def delta_norms(self) -> Dict[str, float]:
        return self._by_leaf(
            [_difference_norm(p._data, make_leaf(self.cfg, self.seed, i))
             for i, _, p in self._held])

    def free(self) -> None:
        from paddle_tpu import obs

        counts = np.asarray(self.model.tokens_per_expert())
        pairs = np.asarray(self.model.pairs_routed())
        obs.instant("moe.tokens_per_expert", counts=counts.tolist())
        obs.instant("moe.pairs_routed", pairs=pairs.tolist())
        self.model = self.opt = self._step = self.params = None
        jax.clear_caches()
        gc.collect()
