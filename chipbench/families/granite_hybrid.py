"""Family ``granite_hybrid``: Granite-4.0-H decoders (a published
``config.json`` of ``model_type`` granitemoehybrid with no routed
experts), run through the program's ``GraniteHybridForCausalLM`` on the
TRAINING path, by job kind ``train`` (nothing in the family is discrete:
no router, no block selection, so the reference runs free as the dense
cell's does). There is no serving: the program has no cache for this
family.

All the benchmark knows of how this family meets the program, as
``families/minicpm_sala.py``: which parameter of the program is which
weight of the reference, how the train step is built, how the plain
reference (``granite_hybrid_reference.py``) is given the same weights and
the same SHARE (``held``: ``layers`` from ``first_layer`` on,
``vocab_rows``). Every leaf is trained; gains and ``D`` start at one,
matrices at N(0, 0.02) times ``changed.weights.scales``. Two kinds of
seeded leaf beyond ``weights.leaf``'s, both as Mamba-2 publishes its
start (``changed.weights.ssm_start``): ``a_log`` is ``log A`` with ``A``
uniform between the two ``A`` numbers, ``dt_bias`` the inverse softplus
of a ``dt`` drawn log-uniformly between the two ``dt`` numbers.
``training.recompute`` states what a block recomputes in its backward
pass: ``"mlp"``, the one thing the program's decoder does.
"""
from __future__ import annotations

import gc
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import shapes_granite, weights
from ..schedule import seed_words
from . import gpt2, gpt_reference, granite_hybrid_reference, zaya
from .gpt2 import _difference_norm
from .zaya import _MIN_ROUNDINGS, _scale

MAMBA = granite_hybrid_reference.MAMBA


def sizes(cfg: Dict) -> Dict:
    """The sizes as run. ``layer_kinds``: each held block's
    ``layer_types`` entry."""
    held = cfg["held"]
    first = held["first_layer"]
    return dict(
        hidden=cfg["hidden_size"], inner=cfg["shared_intermediate_size"],
        layers=held["layers"], first_layer=first,
        layer_kinds=list(cfg["layer_types"][first:first + held["layers"]]),
        q_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        ssm_heads=cfg["mamba_n_heads"], ssm_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], taps=cfg["mamba_d_conv"],
        chunk=cfg["mamba_chunk_size"], eps=cfg["rms_norm_eps"],
        embed_scale=cfg["embedding_multiplier"],
        residual_scale=cfg["residual_multiplier"],
        attn_scale=cfg["attention_multiplier"],
        logit_divide=cfg["logits_scaling"],
        vocab=held["vocab_rows"], vocab_rows=held["vocab_rows"])


def _block_leaves(z: Dict, kind: str):
    """(reference name, program name, shape, kind) of one block."""
    h, i = z["hidden"], z["inner"]
    if kind == MAMBA:
        heads, n = z["ssm_heads"], z["ssm_state"]
        inner = heads * z["ssm_dim"]
        conv = inner + 2 * n
        mixer = [
            ("ssm.w_in", "mamba.in_proj.weight",
             (h, inner + conv + heads), "normal"),
            ("ssm.conv.w", "mamba.conv1d_weight", (z["taps"], conv),
             "normal"),
            ("ssm.conv.b", "mamba.conv1d_bias", (conv,), "normal"),
            ("ssm.dt_bias", "mamba.dt_bias", (heads,), "dt_bias"),
            ("ssm.a_log", "mamba.A_log", (heads,), "a_log"),
            ("ssm.d", "mamba.D", (heads,), "ones"),
            ("ssm.norm.g", "mamba.norm_weight", (inner,), "ones"),
            ("ssm.w_out", "mamba.out_proj.weight", (inner, h), "normal"),
        ]
    else:
        q, k = (z["q_heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"])
        mixer = [
            ("wq", "self_attn.q_proj.weight", (h, q), "normal"),
            ("wk", "self_attn.k_proj.weight", (h, k), "normal"),
            ("wv", "self_attn.v_proj.weight", (h, k), "normal"),
            ("wo", "self_attn.o_proj.weight", (q, h), "normal"),
        ]
    return [
        ("norm_in.g", "input_layernorm.weight", (h,), "ones"),
        *mixer,
        ("norm_post.g", "post_attention_layernorm.weight", (h,), "ones"),
        ("w1", "shared_mlp.gate_proj.weight", (h, i), "normal"),
        ("w3", "shared_mlp.up_proj.weight", (h, i), "normal"),
        ("w2", "shared_mlp.down_proj.weight", (i, h), "normal"),
    ]


def leaves(cfg: Dict):
    """(group, reference name, program name, shape, kind) of every seeded
    array, in the fixed order that numbers them: the parameters AdamW
    holds, all of them (the tied head is ``embed/wte`` again). Blocks are
    numbered from 0 as held."""
    z = sizes(cfg)
    out = [("embed", "wte", "model.embed_tokens.weight",
            (z["vocab_rows"], z["hidden"]), "normal")]
    for n, kind in enumerate(z["layer_kinds"]):
        out += [(f"h.{n}", ref, f"model.layers.{n}.{prog}", shape, leaf_kind)
                for ref, prog, shape, leaf_kind in _block_leaves(z, kind)]
    out.append(("head", "norm_f.g", "model.norm.weight", (z["hidden"],),
                "ones"))
    return out


def matrix_leaves(cfg: Dict) -> List[str]:
    """The matrices whose change after two steps is held to its
    expectation: ``zaya.matrix_leaves``' rule on this family's leaves."""
    o = cfg["optimizer"]
    noisy = (o["stochastic_rounding"] and not o["master_weights"]
             and cfg["dtype"] == "bfloat16")
    out = []
    for g, ref, _, shape, _ in leaves(cfg):
        if len(shape) < 2:
            continue
        std = weights.INIT_STD * _scale(cfg, ref)
        jumps = int(np.prod(shape)) * o["lr"] / (2.0 ** -8 * std)
        if not noisy or jumps >= _MIN_ROUNDINGS:
            out.append(f"{g}/{ref}")
    return out


def total_params(cfg: Dict) -> int:
    """Parameters as run."""
    return sum(int(np.prod(l[3])) for l in leaves(cfg))


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    return shapes_granite.train_flops_per_token(sizes(cfg), seq)


def ssd_counters():
    """``carry`` [mamba layers] of the LAST ``ssd.chunk_carry`` event in
    the program's ring (``Trainer.free()`` records it), or None where the
    program recorded none."""
    try:
        from paddle_tpu import obs
    except ImportError:
        return None
    last = None
    for e in obs.ring().dump():
        if e.get("name") == "ssd.chunk_carry":
            last = e["args"]["carry"]
    return last


# -- seeded weights ----------------------------------------------------------


def _leaf(lo, hi, index, shape, kind, scale, dtype, start):
    """Seeded array ``index`` (module docstring for the two kinds this
    family adds to ``zaya._leaf``'s). Traceable in ``lo``, ``hi``,
    ``index``."""
    if kind not in ("a_log", "dt_bias"):
        return zaya._leaf(lo, hi, index, shape, kind, scale, dtype)
    u = jax.random.uniform(jax.random.fold_in(weights._key(lo, hi), index),
                           shape, jnp.float32)
    if kind == "a_log":
        low, high = start[0]
        return jnp.log(low + (high - low) * u).astype(dtype)
    low, high = (math.log(v) for v in start[1])
    dt = jnp.exp(low + (high - low) * u)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


_leaf_jit = jax.jit(_leaf, static_argnums=(3, 4, 5, 6, 7))


def _statics(cfg: Dict):
    dtype = jnp.dtype(cfg["dtype"])
    start = cfg["changed"]["weights"]["ssm_start"]
    start = (tuple(start["A"]), tuple(start["dt"]))
    return [(tuple(shape), kind, _scale(cfg, ref), dtype, start)
            for _, ref, _, shape, kind in leaves(cfg)]


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


def reference(cfg: Dict, seed: int, precision: str = "f32"):
    """The plain reference on the seeded weights (each group made again
    from the seed in the stored type, then widened)."""
    z = sizes(cfg)
    groups: Dict[str, List[Tuple[int, str]]] = {}
    for i, leaf in enumerate(leaves(cfg)):
        groups.setdefault(leaf[0], []).append((i, leaf[1]))

    def made(group):
        return {ref: make_leaf(cfg, seed, i).astype(jnp.float32)
                for i, ref in groups[group]}

    return granite_hybrid_reference.Reference(
        made, kinds=z["layer_kinds"],
        ssm=dict(heads=z["ssm_heads"], d=z["ssm_dim"], n=z["ssm_state"]),
        attn=dict(nq=z["q_heads"], nkv=z["kv_heads"], d=z["head_dim"],
                  scale=z["attn_scale"]),
        eps=z["eps"], scale=z["residual_scale"],
        embed_scale=z["embed_scale"], divide=z["logit_divide"],
        precision=precision)


def _decoder():
    """The program's classes for this family; an ``ImportError`` where the
    program has no such decoder."""
    from paddle_tpu.models import (GraniteHybridConfig,
                                   GraniteHybridForCausalLM)

    return GraniteHybridConfig, GraniteHybridForCausalLM


def reference_training(cfg: Dict, seed: int, batches, precision="f32") -> Dict:
    """The two AdamW steps the ``train`` job follows. It is the job's
    first call into the family, so a program without the decoder (the
    parent commit) fails HERE, at once, and not after the reference's two
    minutes (PR 40's first chip call: exit code 1 after 1 min 58 s)."""
    _decoder()
    return gpt_reference.train_two_steps(
        reference(cfg, seed, precision), batches, cfg["optimizer"],
        cfg["dtype"])


# -- the program -------------------------------------------------------------


def _build_model(cfg: Dict, seed: int):
    """``GraniteHybridForCausalLM`` at the published widths holding the
    seeded weights of its share, as ``minicpm_sala._build_model``. Returns
    the model and its parameters in ``leaves`` order."""
    import paddle_tpu as paddle
    from paddle_tpu.nn.layer import layers as _layers

    GraniteHybridConfig, GraniteHybridForCausalLM = _decoder()

    if cfg["training"]["recompute"] != "mlp":
        raise ValueError("training.recompute "
                         f"{cfg['training']['recompute']!r}: the program's "
                         "decoder recomputes each block's MLP half")
    z = sizes(cfg)
    paddle.seed(seed % (1 << 31))
    with paddle.LazyGuard():
        model = GraniteHybridForCausalLM(GraniteHybridConfig(
            vocab_size=cfg["vocab_size"], hidden_size=z["hidden"],
            shared_intermediate_size=z["inner"],
            num_hidden_layers=cfg["num_hidden_layers"],
            layer_types=list(cfg["layer_types"]),
            num_attention_heads=z["q_heads"],
            num_key_value_heads=z["kv_heads"],
            mamba_n_heads=z["ssm_heads"], mamba_d_head=z["ssm_dim"],
            mamba_d_state=z["ssm_state"], mamba_d_conv=z["taps"],
            mamba_n_groups=cfg["mamba_n_groups"],
            mamba_expand=cfg["mamba_expand"], mamba_chunk_size=z["chunk"],
            rms_norm_eps=z["eps"], embedding_multiplier=z["embed_scale"],
            residual_multiplier=z["residual_scale"],
            attention_multiplier=z["attn_scale"],
            logits_scaling=z["logit_divide"],
            first_layer=z["first_layer"], held_layers=z["layers"],
            vocab_rows=z["vocab_rows"]))
    named = dict(model.named_parameters())
    want = leaves(cfg)
    if sorted(named) != sorted(l[2] for l in want):
        raise RuntimeError(
            "the program's parameters are not the family's leaves: "
            f"{sorted(set(named) ^ {l[2] for l in want})[:6]}")
    model._cast_params(cfg["dtype"])
    params = []
    for (_, _, prog, shape, _), arr in zip(want, make_all(cfg, seed)):
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
    """The compiled train step with its state: ``jit.to_static`` over the
    model and AdamW as ``cfg['optimizer']`` says; ONE object serves the
    checked first steps and the measured window. ``free()`` reads the
    Mamba layers' ``chunk_carry`` off the device ONCE and records it into
    ``obs.ring()`` as ``ssd.chunk_carry`` (``carry`` [mamba layers])."""

    def __init__(self, cfg: Dict, seed: int):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as popt
        from paddle_tpu.tensor import manipulation as M

        self._paddle = paddle
        self.cfg, self.seed = cfg, seed
        model, self.params = _build_model(cfg, seed)
        self._held = [(i, f"{leaf[0]}/{leaf[1]}", p) for i, (leaf, p)
                      in enumerate(zip(leaves(cfg), self.params))]
        o = cfg["optimizer"]
        opt = popt.AdamW(
            learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
            epsilon=o["eps"], weight_decay=o["weight_decay"],
            parameters=self.params,
            multi_precision=o["master_weights"],
            use_stochastic_rounding=o["stochastic_rounding"],
            moment_dtype=o["moment_dtype"])

        def step(ids, labels):
            logits = model(ids)
            b, s, v = logits.shape
            loss = F.cross_entropy(
                M.reshape(logits, [b * s, v]), M.reshape(labels, [b * s]))
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        self.model, self.opt = model, opt
        self._step = paddle.jit.to_static(
            step, layers=[model], optimizers=[opt])

    step = gpt2.Trainer.step      # a step that returns its loss alone

    def delta_norms(self) -> Dict[str, float]:
        return self._by_leaf(
            [_difference_norm(p._data, make_leaf(self.cfg, self.seed, i))
             for i, _, p in self._held])

    def free(self) -> None:
        from paddle_tpu import obs

        obs.instant("ssd.chunk_carry", carry=np.asarray(
            self.model.chunk_carry()).tolist())
        self.model = self.opt = self._step = self.params = None
        jax.clear_caches()
        gc.collect()
