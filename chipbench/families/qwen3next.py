"""Family ``qwen3next``: Qwen3-Next decoders (a published ``config.json``
of ``model_type`` qwen3_next), run through the program's
``Qwen3NextForCausalLM`` on the TRAINING path. The serving functions
raise ``NotImplementedError``: the program has no cache for this family.

All the benchmark knows of how this family meets the program, as
``families/afmoe.py`` (whose share under ``held`` it keeps; every leaf
is trained, and nothing stands in for a trained router's balance: the
configuration says what was read): which parameter of the program is
which weight of the reference, how the train step is built, how the
seeded weights get their scales, how the plain reference
(``qwen3next_reference.py``) is given the same weights and the same
SHARE. ``training.recompute`` is what a block recomputes in its backward
pass.

A zero-centred norm's ``g`` starts at its published 0 (``zeros``). One
kind of seeded leaf beyond ``weights.leaf``'s: ``decay`` is ``A_log``
[value heads]: ``exp(A_log)`` runs log-evenly over the heads between the
two ``changed.weights.decay_rates``, times a seeded lognormal jitter (the
published start, uniform in (0, 16), forgets the state inside a chunk:
the configuration says why that will not do).

A routed block's choice is a SET of ``top_k`` experts a token; ten ids
of nine bits do not fit ``afmoe.pack``'s integer, so ``pack`` here makes
ONE element of a set another way: the ids ascending as ``uint16``, viewed
as one void scalar of 2 k bytes. Job kind ``train_routed`` compares such
arrays with ``!=`` and hands them back untouched, so it serves unchanged
and its ``route_flip_share.mean`` is the share of (token, routed block)
pairs whose chosen SET differs.
"""
from __future__ import annotations

import gc
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import shapes_qwen3next, weights
from ..schedule import seed_words
from . import afmoe_reference, gpt_reference, qwen3next_reference, zaya
from .afmoe import (  # noqa: F401 (the serving refusals are the family's too)
    Server, control_gaps, kv_bytes_per_token, moe_counters, served_gaps)
from .gpt2 import _difference_norm
from .zaya import _MIN_ROUNDINGS, _scale

LINEAR = qwen3next_reference.LINEAR


def sizes(cfg: Dict) -> Dict:
    """The sizes as run, under the names ``afmoe.sizes`` uses where the
    thing is the same. ``layer_kinds``: each held block's
    ``layer_type``."""
    held = cfg["held"]
    first = held["first_layer"]
    n = cfg["full_attention_interval"]
    kinds = ["full_attention" if (i + 1) % n == 0 else LINEAR
             for i in range(first, first + held["layers"])]
    return dict(
        hidden=cfg["hidden_size"], layers=held["layers"], first_layer=first,
        layer_kinds=kinds, q_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        heads=cfg["num_attention_heads"],
        rot=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        key_heads=cfg["linear_num_key_heads"],
        value_heads=cfg["linear_num_value_heads"],
        key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"],
        taps=cfg["linear_conv_kernel_dim"],
        experts=held["experts"], first_expert=held["first_expert"],
        published_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        expert_inner=cfg["moe_intermediate_size"],
        shared_inner=cfg["shared_expert_intermediate_size"],
        theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        vocab=held["vocab_rows"], vocab_rows=held["vocab_rows"],
        recompute=cfg["training"]["recompute"])


def _block_leaves(z: Dict, kind: str):
    """(group suffix, reference name, program name, shape, kind, name its
    scale goes by) of one block; the two stacked expert matrices are
    groups of their own, as ``afmoe._block_leaves``."""
    h = z["hidden"]
    if kind == LINEAR:
        hv = z["value_heads"]
        key, value = z["key_heads"] * z["key_dim"], hv * z["value_dim"]
        mixer = [
            ("gdn.w_qkvz", "linear_attn.in_proj_qkvz.weight",
             (h, 2 * key + 2 * value), "normal"),
            ("gdn.w_ba", "linear_attn.in_proj_ba.weight", (h, 2 * hv),
             "normal"),
            ("gdn.conv", "linear_attn.conv1d_weight",
             (z["taps"], 2 * key + value), "normal"),
            ("gdn.a_log", "linear_attn.A_log", (hv,), "decay"),
            ("gdn.dt_bias", "linear_attn.dt_bias", (hv,), "ones"),
            ("gdn.norm.g", "linear_attn.norm_weight", (z["value_dim"],),
             "ones"),
            ("gdn.wo", "linear_attn.out_proj.weight", (value, h), "normal"),
        ]
    else:
        d = z["head_dim"]
        q, k = z["q_heads"] * d, z["kv_heads"] * d
        mixer = [
            ("wq", "self_attn.q_proj.weight", (h, 2 * q), "normal"),
            ("wk", "self_attn.k_proj.weight", (h, k), "normal"),
            ("wv", "self_attn.v_proj.weight", (h, k), "normal"),
            ("wo", "self_attn.o_proj.weight", (q, h), "normal"),
            ("q_norm.g", "self_attn.q_norm.weight", (d,), "zeros"),
            ("k_norm.g", "self_attn.k_norm.weight", (d,), "zeros"),
        ]
    e, f, s = z["experts"], z["expert_inner"], z["shared_inner"]
    small = [
        ("norm_in.g", "input_layernorm.weight", (h,), "zeros"),
        *mixer,
        ("norm_post.g", "post_attention_layernorm.weight", (h,), "zeros"),
        ("router.w", "mlp.router.weight", (h, z["published_experts"]),
         "normal"),
        ("shared.w1", "mlp.shared_expert.gate_proj.weight", (h, s), "normal"),
        ("shared.w3", "mlp.shared_expert.up_proj.weight", (h, s), "normal"),
        ("shared.w2", "mlp.shared_expert.down_proj.weight", (s, h),
         "normal"),
        ("shared.gate", "mlp.shared_expert_gate.weight", (h, 1), "normal"),
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
    parameters AdamW holds, all of them. Blocks are numbered from 0 as held."""
    z = sizes(cfg)
    out = [("embed", "wte", "model.embed_tokens.weight",
            (z["vocab_rows"], z["hidden"]), "normal", "wte")]
    for n, kind in enumerate(z["layer_kinds"]):
        out += [(f"h.{n}{suffix}", ref, f"model.layers.{n}.{prog}", shape,
                 leaf_kind, scaled)
                for suffix, ref, prog, shape, leaf_kind, scaled
                in _block_leaves(z, kind)]
    out += [("head", "norm_f.g", "model.norm.weight", (z["hidden"],), "zeros",
             "norm_f.g"),
            ("head", "lm_head", "lm_head.weight",
             (z["hidden"], z["vocab_rows"]), "normal", "lm_head")]
    return out


leaves = _all_leaves


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
    """Parameters as run."""
    return sum(int(np.prod(l[3])) for l in _all_leaves(cfg))


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    return shapes_qwen3next.train_flops_per_token(sizes(cfg), seq)


# -- seeded weights ----------------------------------------------------------


def _leaf(lo, hi, index, shape, kind, scale, dtype, rates):
    """Seeded array ``index`` (module docstring for the kind this family
    adds to ``zaya._leaf``'s). Traceable in ``lo``, ``hi``, ``index``."""
    if kind == "decay":
        ramp = jnp.linspace(math.log(rates[0]), math.log(rates[1]), shape[0])
        jitter = weights.leaf(lo, hi, index, shape, "normal", jnp.float32)
        return (ramp + jitter * scale).astype(dtype)
    return zaya._leaf(lo, hi, index, shape, kind, scale, dtype)


_leaf_jit = jax.jit(_leaf, static_argnums=(3, 4, 5, 6, 7))


def _statics(cfg: Dict):
    dtype = jnp.dtype(cfg["dtype"])
    rates = tuple(cfg["changed"]["weights"]["decay_rates"])
    return [(tuple(shape), kind, _scale(cfg, scaled), dtype, rates)
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


# -- a token's set of experts as one element ---------------------------------


def pack(ids) -> np.ndarray:
    """ids [..., k] (any order) -> [...] of void scalars, 2 k bytes each:
    the SET, its ids ascending as uint16. Two such arrays compare with
    ``==`` / ``!=`` element by element, a set at a time."""
    ids = np.ascontiguousarray(np.sort(np.asarray(ids), axis=-1), np.uint16)
    return ids.view(np.dtype((np.void, 2 * ids.shape[-1])))[..., 0]


def unpack(sets) -> np.ndarray:
    """``pack``'s inverse: [...] -> [..., k] int32, ascending."""
    sets = np.ascontiguousarray(sets)
    return sets[..., None].view(np.uint16).astype(np.int32)


# -- the plain reference, on the same seeded weights and the same share ------


def reference(cfg: Dict, seed: int, precision: str = "f32", follow=None):
    """The plain reference on the seeded weights, as ``afmoe.reference``:
    ``follow`` is, for each training forward it will make, the [routed
    blocks, B, S] packed sets the program's tokens met."""
    z = sizes(cfg)
    groups: Dict[str, List[Tuple[int, str]]] = {}
    for i, leaf in enumerate(_all_leaves(cfg)):
        groups.setdefault(leaf[0], []).append((i, leaf[1]))
    if follow is not None:
        follow = [unpack(f) for f in follow]

    def made(group):
        return {ref: make_leaf(cfg, seed, i).astype(jnp.float32)
                for i, ref in groups[group]}

    return qwen3next_reference.Reference(
        made,
        layers=[qwen3next_reference.Layer(k) for k in z["layer_kinds"]],
        gdn=dict(hk=z["key_heads"], hv=z["value_heads"], dk=z["key_dim"],
                 dv=z["value_dim"]),
        attn=dict(nq=z["q_heads"], nkv=z["kv_heads"], d=z["head_dim"],
                  theta=z["theta"], rot=z["rot"]),
        eps=z["eps"], top_k=z["top_k"], first_expert=z["first_expert"],
        follow=follow, precision=precision)


def reference_training(cfg: Dict, seed: int, batches, precision="f32",
                       follow=None) -> Dict:
    """The two AdamW steps the ``train_routed`` job follows, and the
    reference's OWN routing of the first step's forward (``routing``:
    [routed blocks] of (packed set [B, S], mean chosen probability))."""
    ref = reference(cfg, seed, precision, follow)
    out = gpt_reference.train_two_steps(
        ref, batches, cfg["optimizer"], cfg["dtype"])
    stats = afmoe_reference.routing_stats(
        ref.first_routing, sizes(cfg)["published_experts"])
    out["routing"] = [(pack(e), top) for e, top in ref.first_routing]
    print(f"[chipbench] qwen3next reference ({precision}) routing of step "
          "1: " + " ".join(f"{k}={v:.4f}" for k, v in stats.items()),
          flush=True)
    return out


# -- the program -------------------------------------------------------------


def _build_model(cfg: Dict, seed: int):
    """``Qwen3NextForCausalLM`` at the published widths holding the seeded
    weights of its share, as ``afmoe._build_model``. Returns the model and
    its parameters in ``_all_leaves`` order."""
    import paddle_tpu as paddle
    from paddle_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM
    from paddle_tpu.nn.layer import layers as _layers

    z = sizes(cfg)
    paddle.seed(seed % (1 << 31))
    with paddle.LazyGuard():
        model = Qwen3NextForCausalLM(Qwen3NextConfig(
            vocab_size=z["vocab_rows"], hidden_size=z["hidden"],
            num_hidden_layers=cfg["num_hidden_layers"],
            full_attention_interval=cfg["full_attention_interval"],
            linear_num_key_heads=z["key_heads"],
            linear_num_value_heads=z["value_heads"],
            linear_key_head_dim=z["key_dim"],
            linear_value_head_dim=z["value_dim"],
            linear_conv_kernel_dim=z["taps"],
            num_attention_heads=z["q_heads"],
            num_key_value_heads=z["kv_heads"], head_dim=z["head_dim"],
            partial_rotary_factor=cfg["partial_rotary_factor"],
            rope_theta=z["theta"], rms_norm_eps=z["eps"],
            num_experts=z["published_experts"],
            num_experts_per_tok=z["top_k"],
            norm_topk_prob=cfg["norm_topk_prob"],
            moe_intermediate_size=z["expert_inner"],
            shared_expert_intermediate_size=z["shared_inner"],
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


class Trainer(zaya.Trainer):
    """The compiled train step with its state, as ``afmoe.Trainer``:
    ``jit.to_static`` over the model and AdamW. The step returns, beside
    its loss, the set of experts every token chose in every block, best
    first. ``free()`` reads the experts' counters off
    the device ONCE and records them into ``obs.ring()`` as
    ``moe.tokens_per_expert`` (``counts`` [blocks, held]),
    ``moe.pairs_routed`` (``pairs`` [blocks]) and ``moe.calls_in_full``
    (``calls`` [blocks])."""

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

        self.model, self.opt, self._chosen = model, opt, None
        self._step = paddle.jit.to_static(
            step, layers=[model], optimizers=[opt])

    def routing(self) -> np.ndarray:
        """[blocks, B, S] of packed sets: what every token chose in the
        LAST step (one device-to-host read)."""
        ids = np.asarray(self._chosen._data)
        k = self._z["top_k"]
        ids = ids.reshape(*ids.shape[:-1], ids.shape[-1] // k, k)
        return np.moveaxis(pack(ids), -1, 0)

    def delta_norms(self) -> Dict[str, float]:
        return self._by_leaf(
            [_difference_norm(p._data, make_leaf(self.cfg, self.seed, i))
             for i, _, p in self._held])

    def free(self) -> None:
        from paddle_tpu import obs

        counts = np.asarray(self.model.tokens_per_expert())
        obs.instant("moe.tokens_per_expert", counts=counts.tolist())
        obs.instant("moe.pairs_routed",
                    pairs=np.asarray(self.model.pairs_routed()).tolist())
        obs.instant("moe.calls_in_full",
                    calls=np.asarray(self.model.calls_in_full()).tolist())
        self.model = self.opt = self._step = self.params = None
        jax.clear_caches()
        gc.collect()
