"""Family ``gpt2``: GPT-2/GPT-3 style decoders with a published
``config.json`` of ``model_type`` gpt2 (``n_embd``, ``n_layer``,
``n_head``, ``n_inner``, ``n_positions``, ``vocab_size``,
``layer_norm_epsilon``), run through the program's ``GPTForCausalLM``.

This file is all the benchmark knows of how this family meets the
program: which parameter of the program is which published weight, how
the train step and the engine are built from a configuration file's
``optimizer`` and ``engine`` groups, and how the family's
plain reference (``gpt_reference.py``) is given the same seeded weights.
The jobs (``chipbench/jobs``) call only the functions below.
"""
from __future__ import annotations

import gc
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from .. import shapes, weights
from . import gpt_reference

_BLOCK = [  # published name, program name, shape by (H, I), kind
    ("ln_1.g", "ln_1.weight", lambda h, i: (h,), "ones"),
    ("ln_1.b", "ln_1.bias", lambda h, i: (h,), "zeros"),
    ("c_attn.w", "attn.qkv_proj.weight", lambda h, i: (h, 3 * h), "normal"),
    ("c_attn.b", "attn.qkv_proj.bias", lambda h, i: (3 * h,), "zeros"),
    ("c_proj.w", "attn.out_proj.weight", lambda h, i: (h, h), "normal"),
    ("c_proj.b", "attn.out_proj.bias", lambda h, i: (h,), "zeros"),
    ("ln_2.g", "ln_2.weight", lambda h, i: (h,), "ones"),
    ("ln_2.b", "ln_2.bias", lambda h, i: (h,), "zeros"),
    ("c_fc.w", "fc1.weight", lambda h, i: (h, i), "normal"),
    ("c_fc.b", "fc1.bias", lambda h, i: (i,), "zeros"),
    ("mlp_proj.w", "fc2.weight", lambda h, i: (i, h), "normal"),
    ("mlp_proj.b", "fc2.bias", lambda h, i: (h,), "zeros"),
]


def sizes(cfg: Dict) -> Dict:
    """The sizes as run: the published keys, with the vocabulary held in
    ``held.vocab_rows`` rows (token ids are drawn below ``vocab_size``)."""
    return dict(hidden=cfg["n_embd"], inner=cfg["n_inner"],
                layers=cfg["n_layer"], heads=cfg["n_head"],
                positions=cfg["n_positions"], vocab=cfg["vocab_size"],
                vocab_rows=cfg["held"]["vocab_rows"],
                eps=cfg["layer_norm_epsilon"])


def leaves(cfg: Dict) -> List[Tuple[str, str, str, Tuple[int, ...], str]]:
    """(group, published name, program name, shape, kind) of every
    weight, in the fixed order that numbers the seeded leaves."""
    z = sizes(cfg)
    h, i = z["hidden"], z["inner"]
    out = [("embed", "wte", "transformer.wte.weight",
            (z["vocab_rows"], h), "normal"),
           ("embed", "wpe", "transformer.wpe.weight",
            (z["positions"], h), "normal")]
    for n in range(z["layers"]):
        out += [(f"h.{n}", pub, f"transformer.h.{n}.{prog}", shape(h, i), kind)
                for pub, prog, shape, kind in _BLOCK]
    out += [("head", "ln_f.g", "transformer.ln_f.weight", (h,), "ones"),
            ("head", "ln_f.b", "transformer.ln_f.bias", (h,), "zeros"),
            ("head", "lm_head", "lm_head.weight", (h, z["vocab_rows"]),
             "normal")]
    return out


def _spec(cfg):
    return [(f"{g}/{pub}", shape, kind)
            for g, pub, _, shape, kind in leaves(cfg)]


def matrix_leaves(cfg: Dict) -> List[str]:
    return [f"{g}/{pub}" for g, pub, _, shape, _ in leaves(cfg)
            if len(shape) > 1]


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    z = sizes(cfg)
    return shapes.gpt_train_flops_per_token(
        z["hidden"], z["inner"], z["layers"], z["vocab_rows"], z["heads"],
        seq)


def kv_bytes_per_token(cfg: Dict) -> int:
    z = sizes(cfg)
    return shapes.kv_bytes_per_token(
        z["layers"], z["heads"], z["hidden"] // z["heads"], 2)


# -- the plain reference, on the same seeded weights -----------------------


def reference(cfg: Dict, seed: int, precision: str = "f32"):
    """The family's reference over the weights of ``seed``: each group is
    made again from the seed (in the stored type, then widened)."""
    z, spec = sizes(cfg), _spec(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    index: Dict[str, List[Tuple[int, str]]] = {}
    for i, (group, pub, *_rest) in enumerate(leaves(cfg)):
        index.setdefault(group, []).append((i, pub))

    def get(group):
        return {pub: weights.make_leaf(spec, seed, i, dtype).astype(jnp.float32)
                for i, pub in index[group]}

    return gpt_reference.Reference(
        get, layers=z["layers"], heads=z["heads"], eps=z["eps"],
        precision=precision)


def reference_training(cfg: Dict, seed: int, batches, precision="f32") -> Dict:
    return gpt_reference.train_two_steps(
        reference(cfg, seed, precision), batches, cfg["optimizer"],
        cfg["dtype"])


served_gaps = gpt_reference.served_gaps
control_gaps = gpt_reference.control_gaps


# -- the program -----------------------------------------------------------


def _build_model(cfg: Dict, seed: int):
    """``GPTForCausalLM`` at the published sizes holding the seeded
    weights: built under ``paddle.LazyGuard`` so that the program's own
    float32 initialisation never runs, then handed the arrays made on the
    device in one call."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn.layer import layers as _layers

    z = sizes(cfg)
    paddle.seed(seed % (1 << 31))
    with paddle.LazyGuard():
        model = GPTForCausalLM(GPTConfig(
            vocab_size=z["vocab_rows"], hidden_size=z["hidden"],
            intermediate_size=z["inner"], num_hidden_layers=z["layers"],
            num_attention_heads=z["heads"],
            max_position_embeddings=z["positions"],
            layer_norm_epsilon=z["eps"], dropout=0.0))
    named = dict(model.named_parameters())
    want = leaves(cfg)
    if sorted(named) != sorted(prog for _, _, prog, _, _ in want):
        raise RuntimeError(
            "the program's parameters are not the family's leaves: "
            f"{sorted(set(named) ^ {w[2] for w in want})[:6]}")
    arrays = weights.make_all(_spec(cfg), seed, jnp.dtype(cfg["dtype"]))
    for (_, _, prog, shape, _), arr in zip(want, arrays):
        p = named[prog]
        lazy = getattr(p, "_lazy_init", None)
        if lazy is None or tuple(lazy[1]) != tuple(shape):
            raise RuntimeError(f"{prog}: shape {lazy and lazy[1]} is not "
                               f"the published {shape}")
        p._data, p._lazy_init = arr, None
        _layers._lazy_init_state["pending"] -= 1
    model._cast_params(cfg["dtype"])  # records the dtype; arrays already are
    return model, [named[prog] for _, _, prog, _, _ in want]


class Trainer:
    """The compiled train step with its state: ``jit.to_static`` over the
    model and AdamW as ``cfg['optimizer']`` says. ONE object serves the
    checked first steps and the measured window."""

    def __init__(self, cfg: Dict, seed: int):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as popt
        from paddle_tpu.tensor import manipulation as M

        self._paddle = paddle
        self.cfg, self.seed = cfg, seed
        model, self.params = _build_model(cfg, seed)
        o = cfg["optimizer"]
        opt = popt.AdamW(
            learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
            epsilon=o["eps"], weight_decay=o["weight_decay"],
            parameters=model.parameters(),
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

    def step(self, ids, labels) -> float:
        """One optimizer step on a host batch; returns when the device
        has finished it."""
        t = self._paddle.to_tensor
        loss = self._step(t(ids), t(labels))
        jax.block_until_ready(loss._data)
        return float(loss)

    def leaf_names(self) -> List[str]:
        return [f"{g}/{pub}" for g, pub, *_ in leaves(self.cfg)]

    def grad_norms(self) -> Dict[str, float]:
        """L2 norm of the gradient the optimizer got in its FIRST step,
        worked out from its state after that step: m1 = (1 - beta1) g."""
        m = self.opt._accumulators["moment1"]
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["beta1"])
        norms = _norms([m[p.name] for p in self.params])
        return {n: scale * float(v) for n, v in zip(self.leaf_names(), norms)}

    def moment_norms(self) -> Dict[str, float]:
        """L2 norm of both AdamW moments as the optimizer holds them now:
        ``m/<leaf>`` and ``v/<leaf>``."""
        out = {}
        for tag, key in (("m", "moment1"), ("v", "moment2")):
            acc = self.opt._accumulators[key]
            norms = _norms([acc[p.name] for p in self.params])
            out.update({f"{tag}/{n}": float(v)
                        for n, v in zip(self.leaf_names(), norms)})
        return out

    def delta_norms(self) -> Dict[str, float]:
        """L2 norm of each parameter's change from its seeded value, made
        again from the seed leaf by leaf. The seeded value is an array of
        its own in the stored type: made inside the program that subtracts
        it, XLA may keep it in float32 (it did for ``wte``: the norm then
        holds the rounding of 103M weights, 1.29 times the true change)."""
        spec, dtype = _spec(self.cfg), jnp.dtype(self.cfg["dtype"])
        norms = [_difference_norm(p._data,
                                  weights.make_leaf(spec, self.seed, i, dtype))
                 for i, p in enumerate(self.params)]
        return {n: float(v) for n, v in
                zip(self.leaf_names(), jax.device_get(norms))}

    def free(self) -> None:
        self.model = self.opt = self._step = self.params = None
        jax.clear_caches()
        gc.collect()


@jax.jit
def _norms(arrays):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in arrays]


@jax.jit
def _difference_norm(a, b):
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(d)))


class Server:
    """``ContinuousBatchingEngine`` over the seeded model, given only the
    sizing keywords of ``cfg['engine']``: every behaviour switch of the
    engine stays at the program's default."""

    COUNTERS = ("steps", "decode_tokens", "prefill_tokens", "n_dispatches",
                "h2d_bytes", "d2h_bytes")

    def __init__(self, cfg: Dict, seed: int):
        from paddle_tpu.inference.serving import ContinuousBatchingEngine

        model, _ = _build_model(cfg, seed)
        model.eval()
        e = cfg["engine"]
        self.engine = ContinuousBatchingEngine(
            model, max_batch=e["max_batch"], max_len=e["max_len"],
            block_size=e["block_size"], num_blocks=e["num_blocks"],
            prefill_chunk=e["prefill_chunk"])
        self.num_blocks = e["num_blocks"]

    def submit(self, req_id, prompt, max_new_tokens: int):
        """Hands the request to the engine; returns its handle, whose
        ``out`` list grows as tokens are produced."""
        return self.engine.add_request(req_id, prompt,
                                       max_new_tokens=max_new_tokens)

    def step(self) -> list:
        """One engine iteration; returns the handles that finished."""
        return self.engine.step()

    def idle(self) -> bool:
        return not self.engine._queue and not self.engine.num_active

    def counters(self) -> Dict[str, int]:
        return {k: int(getattr(self.engine, k)) for k in self.COUNTERS}

    def free_blocks(self) -> int:
        return int(self.engine.manager.free_blocks)

    def free(self) -> None:
        self.engine = None
        jax.clear_caches()
        gc.collect()
