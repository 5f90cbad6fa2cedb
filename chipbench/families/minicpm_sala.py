"""Family ``minicpm_sala``: MiniCPM-SALA decoders (a published
``config.json`` of ``model_type`` minicpm_sala), run through the
program's ``MiniCPMSALAForCausalLM`` on the TRAINING path. The serving
functions raise ``NotImplementedError``: the program has no cache for
this family.

All the benchmark knows of how this family meets the program, as
``families/qwen3next.py``: which parameter of the program is which weight
of the reference, how the train step is built, how the plain reference
(``minicpm_sala_reference.py``) is given the same weights and the same
SHARE (``held``: ``layers`` from ``first_layer`` on, ``vocab_rows``).
Every leaf is trained; gains start at one, matrices at N(0, 0.02).
``training.recompute`` states what a block recomputes in its backward
pass: ``"none"``, the one thing the program's decoder does.

The family has no experts. What is discrete in it is a token's SET of
key blocks in a sparse layer, one set a kv group: the compiled step
returns every table, ``Trainer.routing()`` hands them on as [sparse
layers x kv groups, B, S], each element ONE token's set — its (at most)
``topk`` ids ascending as ``uint16``, padding ``0xFFFF``, viewed as one
void scalar (``qwen3next.pack``'s way). Job kind ``train_routed``
compares such arrays with ``!=`` and hands them back untouched, so it
serves unchanged: the reference FOLLOWS the program's sets, and its
``route_flip_share.mean`` is the share of (token, sparse layer, kv group)
whose chosen SET differs from the reference's own.
"""
from __future__ import annotations

import gc
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import shapes_minicpm_sala, weights
from ..schedule import seed_words
from . import gpt_reference, minicpm_sala_reference, zaya
from .gpt2 import _difference_norm
from .zaya import (  # noqa: F401 (the serving refusals are the family's too)
    _MIN_ROUNDINGS, Server, _scale, control_gaps, kv_bytes_per_token,
    served_gaps)

SPARSE = minicpm_sala_reference.SPARSE
_RULE = ("kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
         "window_size")


def sizes(cfg: Dict) -> Dict:
    """The sizes as run. ``layer_kinds``: each held block's
    ``mixer_types`` entry."""
    held = cfg["held"]
    first = held["first_layer"]
    return dict(
        hidden=cfg["hidden_size"], inner=cfg["intermediate_size"],
        layers=held["layers"], first_layer=first,
        layer_kinds=list(cfg["mixer_types"][first:first + held["layers"]]),
        q_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        lin_heads=cfg["lightning_nh"], lin_dim=cfg["lightning_head_dim"],
        rule={k: cfg["sparse_config"][k] for k in _RULE},
        theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        scale_emb=cfg["scale_emb"],
        residual_scale=cfg["scale_depth"] / cfg["num_hidden_layers"] ** 0.5,
        logit_divide=cfg["hidden_size"] / cfg["dim_model_base"],
        vocab=held["vocab_rows"], vocab_rows=held["vocab_rows"])


def _block_leaves(z: Dict, kind: str):
    """(group suffix, reference name, program name, shape, kind) of one
    block; each of the SwiGLU's three matrices (67M numbers) is a group
    of its own, as ``afmoe._block_leaves``' stacked experts are: the
    reference's optimizer holds one group's copies at a time."""
    h, i = z["hidden"], z["inner"]
    if kind == SPARSE:
        d = z["head_dim"]
        q, k = z["q_heads"] * d, z["kv_heads"] * d
        mixer = [
            ("wq", "self_attn.q_proj.weight", (h, q), "normal"),
            ("wk", "self_attn.k_proj.weight", (h, k), "normal"),
            ("wv", "self_attn.v_proj.weight", (h, k), "normal"),
            ("wg", "self_attn.z_proj.weight", (h, q), "normal"),
            ("wo", "self_attn.o_proj.weight", (q, h), "normal"),
            ("q_norm.g", "self_attn.q_norm_weight", (d,), "ones"),
            ("k_norm.g", "self_attn.k_norm_weight", (d,), "ones"),
        ]
    else:
        d = z["lin_dim"]
        w = z["lin_heads"] * d
        mixer = [
            ("lin.wq", "linear_attn.q_proj.weight", (h, w), "normal"),
            ("lin.wk", "linear_attn.k_proj.weight", (h, w), "normal"),
            ("lin.wv", "linear_attn.v_proj.weight", (h, w), "normal"),
            ("lin.wg", "linear_attn.z_proj.weight", (h, w), "normal"),
            ("lin.wo", "linear_attn.o_proj.weight", (w, h), "normal"),
            ("lin.q_norm.g", "linear_attn.q_norm_weight", (d,), "ones"),
            ("lin.k_norm.g", "linear_attn.k_norm_weight", (d,), "ones"),
            ("lin.o_norm.g", "linear_attn.o_norm_weight", (w,), "ones"),
        ]
    small = [
        ("norm_in.g", "input_layernorm.weight", (h,), "ones"),
        *mixer,
        ("norm_post.g", "post_attention_layernorm.weight", (h,), "ones"),
    ]
    return ([("", ref, prog, shape, kind) for ref, prog, shape, kind in small]
            + [(".w1", "w", "mlp.gate_proj.weight", (h, i), "normal"),
               (".w3", "w", "mlp.up_proj.weight", (h, i), "normal"),
               (".w2", "w", "mlp.down_proj.weight", (i, h), "normal")])


def _all_leaves(cfg: Dict):
    """(group, reference name, program name, shape, kind, scale's name)
    of every seeded array, in the fixed order that numbers them: the
    parameters AdamW holds, all of them. Blocks are numbered from 0 as
    held."""
    z = sizes(cfg)
    out = [("embed", "wte", "model.embed_tokens.weight",
            (z["vocab_rows"], z["hidden"]), "normal", "wte")]
    for n, kind in enumerate(z["layer_kinds"]):
        out += [(f"h.{n}{suffix}", ref, f"model.layers.{n}.{prog}", shape,
                 leaf_kind, suffix[1:] or ref)
                for suffix, ref, prog, shape, leaf_kind
                in _block_leaves(z, kind)]
    out += [("head", "norm_f.g", "model.norm.weight", (z["hidden"],), "ones",
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
    return shapes_minicpm_sala.train_flops_per_token(sizes(cfg), seq)


def sparse_counters():
    """(blocks [sparse layers], rows [sparse layers]) of the LAST
    ``sparse.blocks_chosen`` / ``sparse.query_rows`` events in the
    program's ring (``Trainer.free()`` records them), or None where the
    program recorded none."""
    try:
        from paddle_tpu import obs
    except ImportError:
        return None
    last = {}
    for e in obs.ring().dump():
        if e.get("name") in ("sparse.blocks_chosen", "sparse.query_rows"):
            last[e["name"]] = e["args"]
    if len(last) < 2:
        return None
    return (last["sparse.blocks_chosen"]["blocks"],
            last["sparse.query_rows"]["rows"])


# -- seeded weights ----------------------------------------------------------


def _statics(cfg: Dict):
    dtype = jnp.dtype(cfg["dtype"])
    return [(tuple(shape), kind, _scale(cfg, scaled), dtype)
            for _, _, _, shape, kind, scaled in _all_leaves(cfg)]


def make_all(cfg: Dict, seed: int) -> List[jax.Array]:
    """Every seeded array in ONE jitted call, in the stored type."""
    lo, hi = seed_words(seed)
    statics = _statics(cfg)
    return jax.jit(lambda lo, hi: [zaya._leaf(lo, hi, i, *st)
                                   for i, st in enumerate(statics)])(
        jnp.uint32(lo), jnp.uint32(hi))


def make_leaf(cfg: Dict, seed: int, index: int) -> jax.Array:
    """Array ``index`` alone (bit-equal to ``make_all``'s)."""
    lo, hi = seed_words(seed)
    return zaya._leaf_jit(jnp.uint32(lo), jnp.uint32(hi), jnp.uint32(index),
                          *_statics(cfg)[index])


# -- a token's set of blocks as one element ----------------------------------


def pack(ids) -> np.ndarray:
    """ids [..., k] int (any order, < 0: padding) -> [...] of void
    scalars, 2 k bytes each: the SET, its ids ascending as uint16, the
    padding (0xFFFF) last. Two such arrays compare with ``==`` / ``!=``
    element by element, a set at a time."""
    ids = np.sort(np.asarray(ids).astype(np.uint16), axis=-1)
    ids = np.ascontiguousarray(ids)
    return ids.view(np.dtype((np.void, 2 * ids.shape[-1])))[..., 0]


def unpack(sets) -> np.ndarray:
    """``pack``'s inverse: [...] -> [..., k] int32, ascending, padding
    65535."""
    sets = np.ascontiguousarray(sets)
    return sets[..., None].view(np.uint16).astype(np.int32)


# -- the plain reference, on the same seeded weights and the same share ------


def reference(cfg: Dict, seed: int, precision: str = "f32", follow=None):
    """The plain reference on the seeded weights: ``follow`` is, for each
    training forward it will make, the [sparse layers x kv groups, B, S]
    packed sets the program's tokens read."""
    z = sizes(cfg)
    groups: Dict[str, List[Tuple[int, str]]] = {}
    for i, leaf in enumerate(_all_leaves(cfg)):
        groups.setdefault(leaf[0], []).append((i, leaf[1]))
    if follow is not None:
        follow = [unpack(f) for f in follow]

    def made(group):
        return {ref: make_leaf(cfg, seed, i).astype(jnp.float32)
                for i, ref in groups[group]}

    return minicpm_sala_reference.Reference(
        made,
        layers=[minicpm_sala_reference.Layer(k) for k in z["layer_kinds"]],
        lin=dict(heads=z["lin_heads"], d=z["lin_dim"], theta=z["theta"]),
        attn=dict(nq=z["q_heads"], nkv=z["kv_heads"], d=z["head_dim"]),
        rule=z["rule"], eps=z["eps"], scale=z["residual_scale"],
        embed_scale=z["scale_emb"], divide=z["logit_divide"],
        follow=follow, precision=precision)


def reference_training(cfg: Dict, seed: int, batches, precision="f32",
                       follow=None) -> Dict:
    """The two AdamW steps the ``train_routed`` job follows, and the
    reference's OWN sets of the first step's forward (``routing``:
    [sparse layers x kv groups] of (packed set [B, S], blocks it
    holds))."""
    ref = reference(cfg, seed, precision, follow)
    out = gpt_reference.train_two_steps(
        ref, batches, cfg["optimizer"], cfg["dtype"])
    blocks = batches[0][0].shape[1] // sizes(cfg)["rule"]["block_size"]
    out["routing"] = [(pack(np.where(own[:, g] < blocks, own[:, g], -1)),
                       held[:, g])
                      for own, held in ref.first_routing
                      for g in range(own.shape[1])]
    print(f"[chipbench] minicpm_sala reference ({precision}) sets of step "
          "1: blocks a query "
          f"{np.mean([np.mean(h) for _, h in out['routing']]):.3f}",
          flush=True)
    return out


# -- the program -------------------------------------------------------------


def _build_model(cfg: Dict, seed: int):
    """``MiniCPMSALAForCausalLM`` at the published widths holding the
    seeded weights of its share, as ``qwen3next._build_model``. Returns
    the model and its parameters in ``_all_leaves`` order."""
    import paddle_tpu as paddle
    from paddle_tpu.models import MiniCPMSALAConfig, MiniCPMSALAForCausalLM
    from paddle_tpu.nn.layer import layers as _layers

    if cfg["training"]["recompute"] != "none":
        raise ValueError("training.recompute "
                         f"{cfg['training']['recompute']!r}: the program's "
                         "decoder recomputes nothing")
    z = sizes(cfg)
    paddle.seed(seed % (1 << 31))
    with paddle.LazyGuard():
        model = MiniCPMSALAForCausalLM(MiniCPMSALAConfig(
            vocab_size=cfg["vocab_size"], hidden_size=z["hidden"],
            intermediate_size=z["inner"],
            num_hidden_layers=cfg["num_hidden_layers"],
            mixer_types=list(cfg["mixer_types"]),
            num_attention_heads=z["q_heads"],
            num_key_value_heads=z["kv_heads"], head_dim=z["head_dim"],
            lightning_nh=z["lin_heads"], lightning_nkv=cfg["lightning_nkv"],
            lightning_head_dim=z["lin_dim"], rope_theta=z["theta"],
            rms_norm_eps=z["eps"], scale_emb=cfg["scale_emb"],
            scale_depth=cfg["scale_depth"],
            dim_model_base=cfg["dim_model_base"], sparse_config=z["rule"],
            first_layer=z["first_layer"], held_layers=z["layers"],
            vocab_rows=z["vocab_rows"]))
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
    """The compiled train step with its state, as ``qwen3next.Trainer``:
    ``jit.to_static`` over the model and AdamW. The step returns, beside
    its loss, every sparse layer's table. ``free()`` reads the sparse
    layers' counters off the device ONCE and records them into
    ``obs.ring()`` as ``sparse.blocks_chosen`` (``blocks`` [sparse
    layers]) and ``sparse.query_rows`` (``rows`` [sparse layers])."""

    def __init__(self, cfg: Dict, seed: int):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as popt
        from paddle_tpu.tensor import manipulation as M

        self._paddle = paddle
        self.cfg, self.seed = cfg, seed
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
            tables = []
            logits = model(ids, tables)
            b, s, v = logits.shape
            loss = F.cross_entropy(
                M.reshape(logits, [b * s, v]), M.reshape(labels, [b * s]))
            loss.backward()
            opt.step()
            opt.clear_grad()
            # [B, sparse layers x kv groups, S, topk]
            return loss, M.concat(tables, axis=1)

        self.model, self.opt, self._chosen = model, opt, None
        self._step = paddle.jit.to_static(
            step, layers=[model], optimizers=[opt])

    def routing(self) -> np.ndarray:
        """[sparse layers x kv groups, B, S] of packed sets: what every
        token read in the LAST step (one device-to-host read)."""
        return np.moveaxis(pack(np.asarray(self._chosen._data)), 1, 0)

    def delta_norms(self) -> Dict[str, float]:
        return self._by_leaf(
            [_difference_norm(p._data, make_leaf(self.cfg, self.seed, i))
             for i, _, p in self._held])

    def free(self) -> None:
        from paddle_tpu import obs

        obs.instant("sparse.blocks_chosen", blocks=np.asarray(
            self.model.blocks_chosen()).tolist())
        obs.instant("sparse.query_rows", rows=np.asarray(
            self.model.query_rows()).tolist())
        self.model = self.opt = self._step = self.params = None
        jax.clear_caches()
        gc.collect()
