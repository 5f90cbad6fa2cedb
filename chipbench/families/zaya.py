"""Family ``zaya``: ZAYA1 decoders (Zyphra; a published ``config.json`` of
``model_type`` zaya), run through the program's ``ZayaForCausalLM`` on the
TRAINING path. The serving functions raise ``NotImplementedError``: the
program has no cache for this family yet.

All the benchmark knows of how this family meets the program: which
parameter of the program is which weight of the reference, how the train
step is built from a configuration's ``optimizer`` group, how the seeded
weights get their scales (``cfg['changed']['weights']``), and how the
plain reference (``zaya_reference.py``) is given the same weights. The
jobs call only the functions below.

The depth and the vocabulary as run are ``held.layers`` and
``held.vocab_rows``; the published ``num_hidden_layers`` and
``vocab_size`` stay in the file as published. ``frozen`` (a list of
weight-name prefixes, e.g. ``["router."]``) names the weights that the
configuration keeps out of AdamW: they are seeded like the rest, the
program computes no gradient for them, and the reference takes them as
fixed. ``router_balancing.rate`` (absent or 0: none) is the BENCHMARK's
stand-in for a trained router's balance: after each step's routing the
train step built here moves every block's selection bias by
``rebalanced`` below; the program's layers know no such rule.
"""
from __future__ import annotations

import gc
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import shapes_zaya, weights
from ..schedule import seed_words
from . import gpt_reference, zaya_reference
from .gpt2 import _difference_norm, _norms


def sizes(cfg: Dict) -> Dict:
    """The sizes as run. ``vocab`` (ids are drawn below it) and
    ``vocab_rows`` are both the slice held here."""
    rope = cfg["rope_parameters"]["hybrid"]
    return dict(
        hidden=cfg["hidden_size"], layers=cfg["held"]["layers"],
        q_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        heads=cfg["num_attention_heads"],
        experts=cfg["held"]["experts"], top_k=cfg["num_experts_per_tok"],
        expert_inner=cfg["moe_intermediate_size"],
        router_hidden=cfg["router_hidden_size"],
        taps=(cfg["cca_time0"], cfg["cca_time1"]),
        theta=float(rope["rope_theta"]),
        rot=int(cfg["head_dim"] * rope["partial_rotary_factor"]),
        vocab=cfg["held"]["vocab_rows"],
        vocab_rows=cfg["held"]["vocab_rows"], eps=cfg["rms_norm_eps"],
        balance_rate=float(cfg.get("router_balancing", {}).get("rate", 0)))


def _block_leaves(z: Dict):
    """(group suffix, reference name, program name, shape, kind, name its
    scale goes by) of one block. The two stacked expert matrices are
    groups of their own (``h.<n>.gu``, ``h.<n>.dn``, one leaf ``w``
    each): the reference follows AdamW group by group, and a group's
    float32 copies (parameters, moments, two steps) have to stay well
    under what the program itself holds on the device."""
    h, d, r = z["hidden"], z["head_dim"], z["router_hidden"]
    q, k = z["q_heads"] * d, z["kv_heads"] * d
    e, f = z["experts"], z["expert_inner"]
    k0, k1 = z["taps"]
    small = [
        ("norm1.g", "input_layernorm.weight", (h,), "ones"),
        ("wq", "self_attn.q_proj.weight", (h, q), "normal"),
        ("wk", "self_attn.k_proj.weight", (h, k), "normal"),
        ("wv1", "self_attn.v1_proj.weight", (h, k // 2), "normal"),
        ("wv2", "self_attn.v2_proj.weight", (h, k // 2), "normal"),
        ("conv0.w", "self_attn.conv0_weight", (k0, q + k), "normal"),
        ("conv0.b", "self_attn.conv0_bias", (q + k,), "zeros"),
        ("conv1.w", "self_attn.conv1_weight",
         (k1, z["q_heads"] + z["kv_heads"], d, d), "normal"),
        ("conv1.b", "self_attn.conv1_bias", (q + k,), "zeros"),
        ("tau", "self_attn.temperature", (z["kv_heads"],), "ones"),
        ("wo", "self_attn.o_proj.weight", (q, h), "normal"),
        ("norm2.g", "post_attention_layernorm.weight", (h,), "ones"),
        ("router.wd", "router.wd", (h, r), "normal"),
        ("router.bd", "router.bd", (r,), "zeros"),
        ("router.w1", "router.w1", (r, r), "normal"),
        ("router.b1", "router.b1", (r,), "zeros"),
        ("router.w2", "router.w2", (r, r), "normal"),
        ("router.b2", "router.b2", (r,), "zeros"),
        ("router.w3", "router.w3", (r, e), "normal"),
        ("router.b3", "router.b3", (e,), "zeros"),
        # a buffer: seeded like a weight, held by no optimizer
        ("beta", "router.beta", (e,), "buffer"),
    ]
    return ([("", ref, prog, shape, kind, ref)
             for ref, prog, shape, kind in small]
            + [(".gu", "w", "experts.w_gu", (e, h, 2 * f), "normal",
                "experts.w_gu"),
               (".dn", "w", "experts.w_dn", (e, f, h), "normal",
                "experts.w_dn")])


def _all_leaves(cfg: Dict):
    """(group, reference name, program name, shape, kind, scale's name)
    of every seeded array, buffers included, in the fixed order that
    numbers them."""
    z = sizes(cfg)
    out = [("embed", "wte", "model.embed_tokens.weight",
            (z["vocab_rows"], z["hidden"]), "normal", "wte")]
    for n in range(z["layers"]):
        out += [(f"h.{n}{suffix}", ref, f"model.layers.{n}.{prog}", shape,
                 kind, scaled)
                for suffix, ref, prog, shape, kind, scaled
                in _block_leaves(z)]
    out.append(("head", "norm_f.g", "model.norm.weight", (z["hidden"],),
                "ones", "norm_f.g"))
    return out


def _trained(cfg: Dict, leaf) -> bool:
    """Whether AdamW holds this entry of ``_all_leaves``: not a buffer,
    and not under one of the configuration's ``frozen`` prefixes."""
    return leaf[4] != "buffer" and not any(
        leaf[1].startswith(prefix) for prefix in cfg.get("frozen", []))


def leaves(cfg: Dict) -> List[Tuple[str, str, str, Tuple[int, ...], str]]:
    """The PARAMETERS AdamW holds, as ``gpt2.leaves``."""
    return [l for l in _all_leaves(cfg) if _trained(cfg, l)]


_MIN_ROUNDINGS = 2000.0


def matrix_leaves(cfg: Dict) -> List[str]:
    """The matrices whose change after two steps is held to its
    expectation. Where the parameters are bfloat16 written back with
    stochastic rounding, an element of size |p| moves by AdamW's ~lr a
    step only when its write rounds a whole bfloat16 step (2^-8 |p|)
    away, so a leaf's change is the sum of about
    ``elements * lr / (2^-8 * std)`` such jumps a step; a leaf with too
    few of them is too noisy to hold to an expectation, as the vectors
    are (``jobs/train.py::compare_steps``): the router's last matrix
    (4,096 elements of size ~5) makes a few dozen jumps a step."""
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
    return sum(int(np.prod(l[3])) for l in _all_leaves(cfg))


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    return shapes_zaya.train_flops_per_token(sizes(cfg), seq)


# -- seeded weights ----------------------------------------------------------


def _scale(cfg: Dict, name: str) -> float:
    """What the seeded N(0, 0.02) of a leaf is multiplied by
    (``changed.weights.scales``; 1 where the file names none)."""
    return float(cfg["changed"]["weights"]["scales"].get(name, 1.0))


def _leaf(lo, hi, index, shape, kind, scale, dtype):
    """Seeded array ``index``: ``weights.leaf``'s (a ``buffer`` is seeded
    like a ``normal``) times ``scale``. Traceable in ``lo``, ``hi`` and
    ``index``."""
    kind = "normal" if kind == "buffer" else kind
    raw = weights.leaf(lo, hi, index, shape, kind, jnp.float32)
    return (raw * scale).astype(dtype)


_leaf_jit = jax.jit(_leaf, static_argnums=(3, 4, 5, 6))


def _statics(cfg: Dict):
    """(shape, kind, scale, type) of every seeded array: the parameters
    in the configuration's type, a buffer (the router's ``beta``, which
    the balancing rule moves by thousandths) in float32."""
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
    """Array ``index`` alone (bit-equal to ``make_all``'s); one compile
    for each distinct shape."""
    lo, hi = seed_words(seed)
    return _leaf_jit(jnp.uint32(lo), jnp.uint32(hi), jnp.uint32(index),
                     *_statics(cfg)[index])


# -- the plain reference, on the same seeded weights -------------------------


def rebalanced(beta, ids, rate: float):
    """The balancing rule: ``beta`` [E] float32 after one step whose
    tokens met the experts ``ids`` (any shape, int):
    ``beta_e += rate * clip(1 - load_e / even share, -1, 1)``."""
    experts = beta.shape[0]
    load = jnp.bincount(ids.reshape(-1), length=experts).astype(jnp.float32)
    return beta + rate * jnp.clip(1.0 - load * (experts / ids.size),
                                  -1.0, 1.0)


def reference(cfg: Dict, seed: int, precision: str = "f32", follow=None):
    """The plain reference on the seeded weights. ``follow``: for each
    training forward it will make, the [blocks, B, S] experts the
    program's tokens met (``zaya_reference``: "FOLLOWING a routing");
    the selection bias of forward n is then the seeded one moved by the
    balancing rule along the n routings before it."""
    z = sizes(cfg)
    trained: Dict[str, List[Tuple[int, str]]] = {}
    untrained: Dict[str, List[Tuple[int, str]]] = {}
    for i, leaf in enumerate(_all_leaves(cfg)):
        (trained if _trained(cfg, leaf) else untrained).setdefault(
            leaf[0], []).append((i, leaf[1]))

    def made(entries):
        return {ref: make_leaf(cfg, seed, i).astype(jnp.float32)
                for i, ref in entries}

    def fixed(block, step):
        out = made(untrained[f"h.{block}"])
        for before in (follow or [])[:step]:
            out["beta"] = rebalanced(
                out["beta"], jnp.asarray(before[block]), z["balance_rate"])
        return out

    return zaya_reference.Reference(
        lambda group: made(trained[group]), fixed,
        layers=z["layers"], nq=z["q_heads"], nkv=z["kv_heads"],
        d=z["head_dim"], theta=z["theta"], rot=z["rot"], eps=z["eps"],
        follow=follow, precision=precision)


def reference_training(cfg: Dict, seed: int, batches, precision="f32",
                       follow=None) -> Dict:
    """The two AdamW steps the ``train`` job follows
    (``gpt_reference.train_two_steps``, which is generic over a reference
    that yields its gradients group by group), and the reference's OWN
    routing of the first step's forward (``routing``: [blocks] of (expert
    [B, S], top probability))."""
    ref = reference(cfg, seed, precision, follow)
    out = gpt_reference.train_two_steps(
        ref, batches, cfg["optimizer"], cfg["dtype"])
    # a temperature is two numbers a block, and its gradient a sum over
    # every token that all but cancels: its first gradient and first
    # moment are held to the reference like any leaf's, its SECOND moment
    # after two steps is not (the second gradient, taken at parameters
    # that were rounded stochastically, reads 3-18% off by seed on the
    # chip, PERF.md section 2)
    out["moment_norm"] = {k: v for k, v in out["moment_norm"].items()
                          if not (k.startswith("v/") and k.endswith("/tau"))}
    out["routing"] = ref.first_routing
    stats = zaya_reference.routing_stats(out["routing"],
                                         sizes(cfg)["experts"])
    print(f"[chipbench] zaya reference ({precision}) routing of step 1: "
          + " ".join(f"{k}={v:.4f}" for k, v in stats.items()), flush=True)
    return out


def _no_serving(*_a, **_k):
    raise NotImplementedError(
        "family zaya: the program has no cache for this family yet; "
        "training cells only")


served_gaps = control_gaps = kv_bytes_per_token = _no_serving


class Server:
    def __init__(self, *_a, **_k):
        _no_serving()


# -- the program -------------------------------------------------------------


def _build_model(cfg: Dict, seed: int):
    """``ZayaForCausalLM`` at the published widths holding the seeded
    weights (built under ``paddle.LazyGuard``, then handed the arrays made
    on the device in one call). Returns the model and its parameters (the
    ``frozen`` ones too) in ``_all_leaves`` order."""
    import paddle_tpu as paddle
    from paddle_tpu.models import ZayaConfig, ZayaForCausalLM
    from paddle_tpu.nn.layer import layers as _layers

    z = sizes(cfg)
    paddle.seed(seed % (1 << 31))
    with paddle.LazyGuard():
        model = ZayaForCausalLM(ZayaConfig(
            vocab_size=z["vocab_rows"], hidden_size=z["hidden"],
            num_hidden_layers=z["layers"], num_attention_heads=z["q_heads"],
            num_key_value_heads=z["kv_heads"], head_dim=z["head_dim"],
            cca_time0=z["taps"][0], cca_time1=z["taps"][1],
            partial_rotary_factor=z["rot"] / z["head_dim"],
            rope_theta=z["theta"], num_experts=z["experts"],
            num_experts_per_tok=z["top_k"],
            moe_intermediate_size=z["expert_inner"],
            router_hidden_size=z["router_hidden"], rms_norm_eps=z["eps"],
            tie_word_embeddings=cfg["tie_word_embeddings"]))
    named = dict(model.named_parameters())
    buffers = dict(model.named_buffers())
    want = _all_leaves(cfg)
    if sorted(named) != sorted(l[2] for l in want if l[4] != "buffer"):
        raise RuntimeError(
            "the program's parameters are not the family's leaves: "
            f"{sorted(set(named) ^ {l[2] for l in leaves(cfg)})[:6]}")
    model._cast_params(cfg["dtype"])   # records the type; the arrays that
    params = []                        # go in below already have theirs
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


class Trainer:
    """The compiled train step with its state, as ``gpt2.Trainer``:
    ``jit.to_static`` over the model and AdamW as ``cfg['optimizer']``
    says, over every parameter the configuration does not name
    ``frozen``, then the balancing rule where the configuration has one.
    The step returns, beside its loss, the expert every token met in
    every block (``routing()`` reads the last step's).
    ``free()`` reads the experts' token counts off the device ONCE and
    records them into ``obs.ring()`` as ``moe.tokens_per_expert``."""

    def __init__(self, cfg: Dict, seed: int):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as popt
        from paddle_tpu.tensor import manipulation as M

        self._paddle = paddle
        self.cfg, self.seed = cfg, seed
        model, every = _build_model(cfg, seed)
        # (position in _all_leaves, comparison name, parameter) of what
        # AdamW holds, in ``leaves`` order
        self._held = []
        seeded = [(i, l) for i, l in enumerate(_all_leaves(cfg))
                  if l[4] != "buffer"]
        for p, (i, leaf) in zip(every, seeded):
            if _trained(cfg, leaf):
                self._held.append((i, f"{leaf[0]}/{leaf[1]}", p))
            else:
                p.stop_gradient = True
        self.params = [p for _, _, p in self._held]
        o, rate = cfg["optimizer"], sizes(cfg)["balance_rate"]
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
                for layer, ids in zip(model.model.layers, chosen):
                    beta = layer.router.beta
                    beta.set_value(rebalanced(beta._data, ids._data, rate))
            return loss, M.concat(chosen, axis=-1)       # [B, S, blocks]

        self.model, self.opt, self._chosen = model, opt, None
        self._step = paddle.jit.to_static(
            step, layers=[model], optimizers=[opt])

    def step(self, ids, labels) -> float:
        t = self._paddle.to_tensor
        loss, self._chosen = self._step(t(ids), t(labels))
        jax.block_until_ready(loss._data)
        return float(loss)

    def routing(self) -> np.ndarray:
        """[blocks, B, S]: the expert every token met in the LAST step,
        as the compiled step returned it (one device-to-host read)."""
        return np.moveaxis(np.asarray(self._chosen._data), -1, 0)

    def _by_leaf(self, per_param) -> Dict[str, float]:
        per_param = np.asarray(jax.device_get(per_param), np.float64)
        return {name: float(v)
                for (_, name, _), v in zip(self._held, per_param)}

    def grad_norms(self) -> Dict[str, float]:
        """L2 norm of the first step's gradient, from the optimizer's
        state after that step: m1 = (1 - beta1) g."""
        m = self.opt._accumulators["moment1"]
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["beta1"])
        norms = self._by_leaf(_norms([m[p.name] for p in self.params]))
        return {n: scale * v for n, v in norms.items()}

    def moment_norms(self) -> Dict[str, float]:
        out = {}
        for tag, key in (("m", "moment1"), ("v", "moment2")):
            acc = self.opt._accumulators[key]
            norms = self._by_leaf(_norms([acc[p.name] for p in self.params]))
            out.update({f"{tag}/{n}": v for n, v in norms.items()})
        return out

    def delta_norms(self) -> Dict[str, float]:
        """L2 norm of each leaf's change from its seeded value, made
        again from the seed parameter by parameter."""
        return self._by_leaf(
            [_difference_norm(p._data, make_leaf(self.cfg, self.seed, i))
             for i, _, p in self._held])

    def tokens_per_expert(self) -> np.ndarray:
        """[blocks, E]: rows each expert was given since the build (one
        device-to-host read)."""
        return np.asarray(self.model.tokens_per_expert())

    def free(self) -> None:
        from paddle_tpu import obs

        counts = self.tokens_per_expert()
        obs.instant("moe.tokens_per_expert", counts=counts.tolist())
        self.model = self.opt = self._step = self.params = None
        jax.clear_caches()
        gc.collect()
