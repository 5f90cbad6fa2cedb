"""Optimizer base + the full optimizer set.

ref: python/paddle/optimizer/optimizer.py:1863 (step), adam.py, adamw.py:493
(fused adamw path), momentum.py, rmsprop.py, …

TPU-native design: update math is raw jnp on the params' arrays inside
``no_grad`` — a handful of fused elementwise XLA ops per parameter.
Accumulators are plain jax arrays held in a nested dict (a pytree), so
``paddle_tpu.jit`` threads the whole optimizer state through the
compiled train step and donates the old buffers (the reference needs
fused multi-tensor CUDA kernels for this; XLA fuses the update chain
automatically). ``multi_precision`` keeps fp32 master weights for
bf16/fp16 params (ref: optimizer.py _create_master_weight).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..base import dtype as _dtypes
from ..base.tape import no_grad
from ..base.tensor import Tensor
from .lr import LRScheduler

__all__ = [
    "Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adagrad", "Adadelta",
    "Adamax", "RMSProp", "Lamb", "NAdam", "RAdam", "Rprop", "ASGD",
]


def _stochastic_round_bf16(x):
    """Unbiased f32 → bf16 rounding: add 16 random bits below the bf16
    mantissa cut, truncate. Sign-magnitude format makes the trick
    unbiased for both signs (|x| rounds up with probability equal to
    the discarded fraction, so E[result] == x). This is the standard
    masterless-bf16 training recipe: the expected update survives even
    when each step's delta is smaller than one bf16 ulp, replacing the
    8 bytes/param of fp32-master HBM traffic with 16 random bits.
    inf/NaN pass through unperturbed.

    Bit source: a lowbias32-style integer hash over (lane index, two
    per-call threefry salts drawn from the global generator) — measured
    ~10x cheaper inside the fused optimizer pass than a full per-element
    threefry draw (which cost more than the master traffic it
    replaced); rounding noise needs per-element uniformity, not
    cryptographic streams."""
    import jax

    from ..base import random as _random

    xf = x.astype(jnp.float32)
    u = jax.lax.bitcast_convert_type(xf, jnp.uint32)
    salt = jax.random.bits(_random.next_key(), (2,), jnp.uint32)
    i = jax.lax.iota(jnp.uint32, x.size).reshape(x.shape)
    b = i * jnp.uint32(0x9E3779B9) + salt[0]
    b = (b ^ (b >> 16)) * jnp.uint32(0x7FEB352D)
    b = (b ^ (b >> 15)) * jnp.uint32(0x846CA68B)
    b = (b ^ (b >> 16)) + salt[1]
    r = jax.lax.bitcast_convert_type(
        (u + (b & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000),
        jnp.float32,
    )
    return jnp.where(jnp.isfinite(xf), r, xf).astype(jnp.bfloat16)


class L2Decay:
    """ref: python/paddle/regularizer.py L2Decay — grad += coeff * param."""

    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)

    def __call__(self, param, grad):
        return grad + self.coeff * param


class L1Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)

    def __call__(self, param, grad):
        return grad + self.coeff * jnp.sign(param)


class Optimizer:
    _accum_names: List[str] = []

    def __init__(
        self,
        learning_rate=0.001,
        parameters=None,
        weight_decay=None,
        grad_clip=None,
        multi_precision=False,
        name=None,
    ):
        if parameters is None:
            raise ValueError("parameters must be given (dygraph mode requires the param list)")
        self._param_groups = self._normalize_params(parameters)
        self._learning_rate = learning_rate
        self._lr_override = None  # set by paddle_tpu.jit to a traced scalar
        if isinstance(weight_decay, (int, float)):
            self.regularization = L2Decay(float(weight_decay))
        else:
            self.regularization = weight_decay  # L1Decay/L2Decay/None
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        # accumulators: name -> param.name -> jnp array  (a pytree)
        self._accumulators: Dict[str, Dict[str, jnp.ndarray]] = {}
        # when set (by amp.GradScaler around step()), records the init
        # value of every accumulator created during that step so a
        # skipped step can roll them back traceably
        self._accum_creation_log = None
        # placement hooks installed by distributed.sharding (stage 1/2/3)
        # and auto_parallel.shard_optimizer: the accum hook
        # fn(array, param, accum_name) places new optimizer state
        # (including master weights); the grad hook constrains gradient
        # layout, fn(grad_array, param) (stage-2 reduce-scatter)
        self._accum_placement_fn = None
        self._grad_placement_fn = None
        # write low-precision params back with unbiased stochastic
        # rounding (subclasses expose use_stochastic_rounding=True)
        self._stochastic_rounding = False
        self._global_step = 0

    # ------------------------------------------------------------------
    def _normalize_params(self, parameters):
        parameters = list(parameters)
        if parameters and isinstance(parameters[0], dict):
            groups = []
            for g in parameters:
                g = dict(g)
                g["params"] = list(g["params"])
                groups.append(g)
            return groups
        return [{"params": parameters}]

    @property
    def _parameter_list(self):
        out = []
        for g in self._param_groups:
            out.extend(g["params"])
        return out

    # ------------------------------------------------------------------
    # learning rate
    # ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value: float):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    def _lr(self):
        if self._lr_override is not None:
            return self._lr_override
        return self.get_lr()

    # ------------------------------------------------------------------
    # accumulators
    # ------------------------------------------------------------------
    def _get_accum(self, name: str, param, init=None, dtype=None):
        store = self._accumulators.setdefault(name, {})
        key = param.name
        if key not in store:
            if init is None:
                dt = dtype or (
                    jnp.float32 if self._use_master(param) else param._data.dtype
                )
                store[key] = jnp.zeros(param._data.shape, dt)
            else:
                store[key] = init
            if self._accum_placement_fn is not None:
                store[key] = self._accum_placement_fn(store[key], param, name)
            if self._accum_creation_log is not None:
                self._accum_creation_log[(name, key)] = store[key]
        return store[key]

    def _set_accum(self, name: str, param, value):
        self._accumulators[name][param.name] = value

    def _use_master(self, param) -> bool:
        return self._multi_precision and np.dtype(param.dtype) in (
            np.dtype(_dtypes.float16),
            np.dtype(_dtypes.bfloat16),
        )

    def _master_weight(self, param):
        if not self._use_master(param):
            return None
        store = self._accumulators.setdefault("master_weight", {})
        if param.name not in store:
            store[param.name] = param._data.astype(jnp.float32)
            if self._accum_placement_fn is not None:
                store[param.name] = self._accum_placement_fn(
                    store[param.name], param, "master_weight"
                )
            if self._accum_creation_log is not None:
                self._accum_creation_log[("master_weight", param.name)] = store[param.name]
        return store[param.name]

    # ------------------------------------------------------------------
    # step
    # ------------------------------------------------------------------
    @no_grad()
    def step(self):
        # under a trace the scope goes into the op metadata of every update
        # op, so a device trace says what they are (a with-block and not a
        # decorator: one more Python frame under every traced update shifts
        # the interpreter's frame stack, see PERF.md on set-up). The
        # with-block takes a stack slot, so the last loop passes the
        # gradient's array without a local of its own: this frame lies
        # under every traced update and stays at 24 words (PERF.md §6)
        with jax.named_scope("optimizer.step"):
            self._global_step += 1
            for group in self._param_groups:
                params_grads = [
                    (p, p.grad) for p in group["params"] if not p.stop_gradient and p.grad is not None
                ]
                if self._grad_placement_fn is not None:
                    params_grads = [
                        (p, Tensor(self._grad_placement_fn(g._data, p), _internal=True))
                        for p, g in params_grads
                    ]
                # reference order (ref: optimizer.py:1519-1525): grad clip FIRST,
                # then regularization — the decay term is not clipped
                grad_clip = group.get("grad_clip", self._grad_clip)
                if grad_clip is not None:
                    params_grads = grad_clip(params_grads)
                group_reg = group.get("weight_decay", None)
                if isinstance(group_reg, (int, float)):
                    group_reg = L2Decay(float(group_reg))
                new_pg = []
                for p, g in params_grads:
                    # parameter's own regularizer wins, then the group's, then
                    # the optimizer-level one (reference precedence)
                    reg = getattr(p, "regularizer", None) or group_reg or self.regularization
                    if reg is not None:
                        g = Tensor(reg(p._data, g._data), _internal=True)
                    new_pg.append((p, g))
                params_grads = new_pg
                group_lr_scale = float(group.get("learning_rate", 1.0))
                for p, g in params_grads:
                    lr_scale = p.optimize_attr.get("learning_rate", 1.0) if getattr(p, "optimize_attr", None) else 1.0
                    self._update_param(
                        p, g._data if isinstance(g, Tensor) else g,
                        lr_scale * group_lr_scale, group)

    def _update_param(self, p, g, lr_scale, group):
        raise NotImplementedError

    def _apply(self, p, new_value):
        """Write back an update computed in master precision."""
        if self._use_master(p):
            self._accumulators["master_weight"][p.name] = new_value
            p._data = new_value.astype(p._data.dtype)
        elif (
            self._stochastic_rounding
            and p._data.dtype == jnp.bfloat16
            and new_value.dtype != jnp.bfloat16
        ):
            p._data = _stochastic_round_bf16(new_value)
        else:
            p._data = new_value.astype(p._data.dtype)

    def _param_value(self, p):
        mw = self._master_weight(p)
        return mw if mw is not None else p._data

    # ------------------------------------------------------------------
    def clear_grad(self, set_to_zero: bool = False):
        for p in self._parameter_list:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        self.step()
        return None, None

    # ------------------------------------------------------------------
    # state dict
    # ------------------------------------------------------------------
    def state_dict(self):
        sd = {}
        for name, store in self._accumulators.items():
            for pname, arr in store.items():
                sd[f"{pname}.{name}"] = Tensor(arr, _internal=True)
        sd["global_step"] = self._global_step
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        return sd

    def set_state_dict(self, state_dict):
        self._global_step = int(state_dict.get("global_step", 0))
        if "LR_Scheduler" in state_dict and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
        # Saved accumulator keys carry the SAVING process's tensor names
        # (volatile: auto-generated, counter-dependent). A restoring
        # process's params usually have different auto-names, so identify
        # parameters POSITIONALLY: the per-accumulator pname order in
        # state_dict follows the saving optimizer's parameter order
        # (accumulators are created in _parameter_list order), which is
        # this optimizer's order too. Without the remap, _get_accum later
        # misses the restored entries and silently reinitializes zero
        # moments — resumed training drifts from the original run.
        per_accum: dict = {}
        for key in state_dict:
            if key in ("global_step", "LR_Scheduler"):
                continue
            pname, _, accum = key.rpartition(".")
            if pname:
                per_accum.setdefault(accum, [])
                if pname not in per_accum[accum]:
                    per_accum[accum].append(pname)
        live_pnames = [p.name for p in self._parameter_list]
        saved_all = {pn for pnames in per_accum.values() for pn in pnames}
        if saved_all and saved_all <= set(live_pnames):
            remap = {}  # names already match (same-process restore)
        else:
            # positional order must come from ONE full-coverage store
            # (each store is in _parameter_list order, but e.g. a
            # multi_precision master_weight store covers only low-
            # precision params and may have been created first — the
            # whole-dict key order would cross-wire parameters)
            ordered = max(per_accum.values(), key=len) if per_accum else []
            remap = (
                dict(zip(ordered, live_pnames))
                if len(ordered) == len(live_pnames)
                else {}  # partial/foreign state: name identity
            )
        for key, val in state_dict.items():
            if key in ("global_step", "LR_Scheduler"):
                continue
            pname, _, accum = key.rpartition(".")
            pname = remap.get(pname, pname)
            if isinstance(val, Tensor):
                val = val._data
            self._accumulators.setdefault(accum, {})[pname] = jnp.asarray(np.asarray(val))

    set_dict = set_state_dict


# ---------------------------------------------------------------------------


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None, grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)

    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv = self._param_value(p)
        self._apply(p, pv - lr * g.astype(pv.dtype))


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None, use_nesterov=False,
                 weight_decay=None, grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv = self._param_value(p)
        g = g.astype(pv.dtype)
        vel = self._get_accum("velocity", p)
        vel = self._momentum * vel + g
        self._set_accum("velocity", p, vel)
        if self._use_nesterov:
            self._apply(p, pv - lr * (g + self._momentum * vel))
        else:
            self._apply(p, pv - lr * vel)


class _AdamBase(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=None,
                 weight_decay=None, grad_clip=None, lazy_mode=False, multi_precision=False, name=None,
                 moment_dtype=None, use_stochastic_rounding=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        # masterless-bf16 mode: unbiased stochastic-rounded writes let
        # bf16 weights carry the update without fp32 masters (see
        # _stochastic_round_bf16); ignored when multi_precision is on
        self._stochastic_rounding = bool(use_stochastic_rounding)
        # TPU-native extension: storage dtype for m/v ("bfloat16" halves
        # the optimizer's HBM traffic — the AdamW pass runs at bandwidth
        # roofline; update ARITHMETIC stays f32 (_moments), and master
        # weights keep full precision, so this is the standard safe
        # low-precision-moments trade)
        self._moment_dtype = (
            None if moment_dtype is None else jnp.dtype(
                {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
                 "float32": jnp.float32}.get(str(moment_dtype), moment_dtype)
            )
        )

    def _moments(self, p, g):
        pv = self._param_value(p)
        # update ARITHMETIC always runs in f32 — bf16 accumulator math
        # (beta powers with 8 mantissa bits, g*g underflow, eps-dominated
        # denominators) diverges after a single step at billion-param
        # scale; only the accumulator STORAGE stays in the param dtype
        # when multi_precision is off (the memory trade the user asked
        # for). beta powers are scalars: always f32.
        compute = jnp.float32 if pv.dtype != jnp.float64 else jnp.float64
        store = self._moment_dtype or pv.dtype
        g = g.astype(compute)
        m = self._get_accum("moment1", p, dtype=self._moment_dtype).astype(compute)
        v = self._get_accum("moment2", p, dtype=self._moment_dtype).astype(compute)
        b1p = self._get_accum("beta1_pow", p, init=jnp.ones((), compute))
        b2p = self._get_accum("beta2_pow", p, init=jnp.ones((), compute))
        b1p = b1p.astype(compute) * self._beta1
        b2p = b2p.astype(compute) * self._beta2
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_accum("moment1", p, m.astype(store))
        self._set_accum("moment2", p, v.astype(store))
        self._set_accum("beta1_pow", p, b1p)
        self._set_accum("beta2_pow", p, b2p)
        return pv, g, m, v, b1p, b2p

    def _adam_delta(self, lr, m, v, b1p, b2p):
        # paddle adam kernel: lr_t = lr * sqrt(1-b2^t)/(1-b1^t);
        # denom = sqrt(v) + eps * sqrt(1-b2^t); computed in f32 (see
        # _moments), cast to the param dtype by the caller's subtract
        lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
        return lr_t * m / (jnp.sqrt(v) + self._epsilon * jnp.sqrt(1 - b2p))


class Adam(_AdamBase):
    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv, g, m, v, b1p, b2p = self._moments(p, g)
        self._apply(p, pv - self._adam_delta(lr, m, v, b1p, b2p))


class AdamW(_AdamBase):
    """Decoupled weight decay (ref: python/paddle/optimizer/adamw.py:493).
    paddle default weight_decay (coeff) = 0.01; apply_decay_param_fun
    filters which params decay."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=None,
                 weight_decay=0.01, lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None, moment_dtype=None,
                 use_stochastic_rounding=False):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters, None, grad_clip, lazy_mode, multi_precision, name,
                         moment_dtype=moment_dtype,
                         use_stochastic_rounding=use_stochastic_rounding)
        self._coeff = float(weight_decay) if not callable(weight_decay) else weight_decay
        self._lr_ratio = lr_ratio
        self._apply_decay_param_fun = apply_decay_param_fun

    def _decay_for(self, p):
        decay = self._coeff
        if self._apply_decay_param_fun is not None and not self._apply_decay_param_fun(p.name):
            decay = 0.0
        if getattr(p, "no_weight_decay", False):
            decay = 0.0
        return decay

    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        if self._lr_ratio is not None:
            lr = lr * self._lr_ratio(p)
        pv, g, m, v, b1p, b2p = self._moments(p, g)
        decay = self._decay_for(p)
        # decay in the f32 compute dtype: a bf16 pv * (1 - lr*decay)
        # round-trips bit-exactly (relative change ~1e-6 is far below
        # bf16's half-ulp), so in the masterless modes the decay would
        # silently never reach the parameter; promoting first lets the
        # f32 `pv - delta` and _apply's SR write carry it unbiasedly
        compute = jnp.float64 if pv.dtype == jnp.float64 else jnp.float32
        pv = pv.astype(compute) * (1.0 - lr * decay)
        self._apply(p, pv - self._adam_delta(lr, m, v, b1p, b2p))


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv = self._param_value(p)
        g = g.astype(pv.dtype)
        mom = self._get_accum("moment", p, init=jnp.full(pv.shape, self._initial, pv.dtype))
        mom = mom + g * g
        self._set_accum("moment", p, mom)
        self._apply(p, pv - lr * g / (jnp.sqrt(mom) + self._epsilon))


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._epsilon = epsilon
        self._rho = rho

    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv = self._param_value(p)
        g = g.astype(pv.dtype)
        E_g = self._get_accum("avg_squared_grad", p)
        E_u = self._get_accum("avg_squared_update", p)
        E_g = self._rho * E_g + (1 - self._rho) * g * g
        update = jnp.sqrt(E_u + self._epsilon) / jnp.sqrt(E_g + self._epsilon) * g
        E_u = self._rho * E_u + (1 - self._rho) * update * update
        self._set_accum("avg_squared_grad", p, E_g)
        self._set_accum("avg_squared_update", p, E_u)
        self._apply(p, pv - lr * update)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv = self._param_value(p)
        g = g.astype(pv.dtype)
        m = self._get_accum("moment", p)
        inf = self._get_accum("inf_norm", p)
        b1p = self._get_accum("beta1_pow", p, init=jnp.ones((), pv.dtype))
        b1p = b1p * self._beta1
        m = self._beta1 * m + (1 - self._beta1) * g
        inf = jnp.maximum(self._beta2 * inf, jnp.abs(g))
        self._set_accum("moment", p, m)
        self._set_accum("inf_norm", p, inf)
        self._set_accum("beta1_pow", p, b1p)
        self._apply(p, pv - (lr / (1 - b1p)) * m / (inf + self._epsilon))


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0, centered=False,
                 parameters=None, weight_decay=None, grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._rho, self._epsilon, self._momentum, self._centered = rho, epsilon, momentum, centered

    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv = self._param_value(p)
        g = g.astype(pv.dtype)
        ms = self._get_accum("mean_square", p)
        mom = self._get_accum("momentum", p)
        ms = self._rho * ms + (1 - self._rho) * g * g
        self._set_accum("mean_square", p, ms)
        if self._centered:
            mg = self._get_accum("mean_grad", p)
            mg = self._rho * mg + (1 - self._rho) * g
            self._set_accum("mean_grad", p, mg)
            denom = jnp.sqrt(ms - mg * mg + self._epsilon)
        else:
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * mom + lr * g / denom
        self._set_accum("momentum", p, mom)
        self._apply(p, pv - mom)


class Lamb(Optimizer):
    """ref: python/paddle/optimizer/lamb.py — layer-wise trust ratio."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, parameters=None, grad_clip=None, exclude_from_weight_decay_fn=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, multi_precision, name)
        self._wd = lamb_weight_decay
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv = self._param_value(p)
        g = g.astype(pv.dtype)
        m = self._get_accum("moment1", p)
        v = self._get_accum("moment2", p)
        b1p = self._get_accum("beta1_pow", p, init=jnp.ones((), pv.dtype))
        b2p = self._get_accum("beta2_pow", p, init=jnp.ones((), pv.dtype))
        b1p, b2p = b1p * self._beta1, b2p * self._beta2
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_accum("moment1", p, m)
        self._set_accum("moment2", p, v)
        self._set_accum("beta1_pow", p, b1p)
        self._set_accum("beta2_pow", p, b2p)
        m_hat = m / (1 - b1p)
        v_hat = v / (1 - b2p)
        wd = 0.0 if (self._exclude_fn is not None and self._exclude_fn(p)) else self._wd
        r = m_hat / (jnp.sqrt(v_hat) + self._epsilon) + wd * pv
        p_norm = jnp.linalg.norm(pv)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
        self._apply(p, pv - lr * trust * r)


class NAdam(_AdamBase):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 momentum_decay=0.004, parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters, weight_decay, grad_clip)
        self._momentum_decay = momentum_decay

    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv = self._param_value(p)
        g = g.astype(pv.dtype)
        # traced step counter (NOT the host _global_step: it would be
        # baked in at trace time under jit and can't be rolled back by a
        # GradScaler-skipped step)
        t = self._get_accum("step", p, init=jnp.zeros((), jnp.float32)) + 1
        self._set_accum("step", p, t)
        mu_t = self._beta1 * (1 - 0.5 * 0.96 ** (t * self._momentum_decay))
        mu_next = self._beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * self._momentum_decay))
        mu_prod = self._get_accum("mu_product", p, init=jnp.ones((), pv.dtype))
        mu_prod = mu_prod * mu_t
        self._set_accum("mu_product", p, mu_prod)
        m = self._get_accum("moment1", p)
        v = self._get_accum("moment2", p)
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * g * g
        self._set_accum("moment1", p, m)
        self._set_accum("moment2", p, v)
        m_hat = mu_next * m / (1 - mu_prod * mu_next) + (1 - mu_t) * g / (1 - mu_prod)
        v_hat = v / (1 - self._beta2 ** t)
        self._apply(p, pv - lr * m_hat / (jnp.sqrt(v_hat) + self._epsilon))


class RAdam(_AdamBase):
    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv, g, m, v, b1p, b2p = self._moments(p, g)
        # traced step counter; beta2**t == b2p (already a traced accum)
        t = self._get_accum("step", p, init=jnp.zeros((), jnp.float32)) + 1
        self._set_accum("step", p, t)
        rho_inf = 2.0 / (1 - self._beta2) - 1
        rho_t = rho_inf - 2 * t * b2p / (1 - b2p)
        m_hat = m / (1 - b1p)
        # rectification gate as a select so the step stays traceable;
        # clamp inside the sqrt to keep the untaken branch finite
        rho_s = jnp.maximum(rho_t, 5.0)
        r = jnp.sqrt(
            ((rho_s - 4) * (rho_s - 2) * rho_inf) / ((rho_inf - 4) * (rho_inf - 2) * rho_s)
        ).astype(pv.dtype)
        v_hat = jnp.sqrt(v / (1 - b2p))
        rect = pv - lr * r * m_hat / (v_hat + self._epsilon)
        plain = pv - lr * m_hat
        self._apply(p, jnp.where(rho_t > 5, rect, plain))


class Rprop(Optimizer):
    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50), parameters=None,
                 etas=(0.5, 1.2), grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, multi_precision, name)
        self._lr_range = learning_rate_range
        self._etas = etas

    def _update_param(self, p, g, lr_scale, group):
        pv = self._param_value(p)
        g = g.astype(pv.dtype)
        prev = self._get_accum("prev_grad", p)
        lrs = self._get_accum("lrs", p, init=jnp.full(pv.shape, self._lr(), pv.dtype))
        sign = jnp.sign(g * prev)
        lrs = jnp.where(sign > 0, jnp.minimum(lrs * self._etas[1], self._lr_range[1]),
                        jnp.where(sign < 0, jnp.maximum(lrs * self._etas[0], self._lr_range[0]), lrs))
        g_eff = jnp.where(sign < 0, jnp.zeros_like(g), g)
        self._set_accum("prev_grad", p, g_eff)
        self._set_accum("lrs", p, lrs)
        self._apply(p, pv - lrs * jnp.sign(g_eff))


class ASGD(Optimizer):
    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, multi_precision, name)
        self._batch_num = batch_num

    def _update_param(self, p, g, lr_scale, group):
        lr = self._lr() * lr_scale
        pv = self._param_value(p)
        self._apply(p, pv - lr * g.astype(pv.dtype))
