"""GradScaler — dynamic loss scaling (ref: python/paddle/amp/grad_scaler.py:617).

TPU-native design: the found-inf check and the step skip are expressed as
``jnp.where`` selects instead of host control flow, so a scaler-wrapped
train step traces cleanly under ``paddle_tpu.jit.to_static`` (the
reference reads ``found_inf`` back to the host via the
check_finite_and_unscale op; that D2H sync would stall the TPU pipeline).
Skipping a step = snapshotting params + accumulators before
``optimizer.step()`` and selecting the old values when inf was found —
XLA turns the selects into a predicated update with no extra traffic.
"""
from __future__ import annotations

import warnings
from enum import Enum
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..base import dtype as _dtypes
from ..base.tape import no_grad
from ..base.tensor import Tensor

__all__ = ["AmpScaler", "GradScaler", "OptimizerState"]


class OptimizerState(Enum):
    INIT = 0
    UNSCALED = 1
    STEPPED = 2


class AmpScaler:
    """ref: python/paddle/amp/grad_scaler.py AmpScaler (base of GradScaler)."""

    def __init__(
        self,
        enable: bool = True,
        init_loss_scaling: float = 2.0**15,
        incr_ratio: float = 2.0,
        decr_ratio: float = 0.5,
        incr_every_n_steps: int = 1000,
        decr_every_n_nan_or_inf: int = 2,
        use_dynamic_loss_scaling: bool = True,
        on_skip=None,
    ):
        if incr_ratio <= 1.0:
            raise ValueError("incr_ratio should be > 1")
        if not 0.0 < decr_ratio < 1.0:
            raise ValueError("decr_ratio should be in (0, 1)")
        self._enable = bool(enable)
        self._use_dynamic_loss_scaling = bool(use_dynamic_loss_scaling) and self._enable
        self._init_loss_scaling = float(init_loss_scaling)
        self._incr_ratio = float(incr_ratio)
        self._decr_ratio = float(decr_ratio)
        self._incr_every_n_steps = int(incr_every_n_steps)
        self._decr_every_n_nan_or_inf = int(decr_every_n_nan_or_inf)
        self._scale = jnp.asarray(self._init_loss_scaling, jnp.float32)
        self._good_steps = jnp.asarray(0, jnp.int32)
        self._bad_steps = jnp.asarray(0, jnp.int32)
        self._found_inf = jnp.asarray(False)
        self._opt_states: Dict[int, OptimizerState] = {}
        # found_inf skip observability: host-side counters advanced at
        # update() time, where the skip decision is settled. Counted
        # only when found_inf is CONCRETE — inside a to_static trace it
        # is a tracer and the threaded device state owns the semantics;
        # callers on that path read _found_inf after the compiled step
        # (jit restores a concrete value) instead of these counters.
        self._n_skipped_steps = 0
        self._last_skip_step = -1
        self._n_updates = 0
        self._on_skip = on_skip

    # ------------------------------------------------------------------
    def is_enable(self) -> bool:
        return self._enable

    is_enabled = is_enable

    def is_use_dynamic_loss_scaling(self) -> bool:
        return self._use_dynamic_loss_scaling

    # ------------------------------------------------------------------
    def scale(self, var):
        """Multiply the loss by the current scale (ref: grad_scaler.py scale)."""
        if not self._enable:
            return var
        return var * Tensor(self._scale.astype(var._data.dtype), _internal=True)

    # ------------------------------------------------------------------
    def _params_with_grads(self, optimizer):
        return [
            p for p in optimizer._parameter_list
            if not p.stop_gradient and p._grad is not None
        ]

    @no_grad()
    def unscale_(self, optimizer):
        """Divide grads by the scale and detect non-finite values
        (check_finite_and_unscale semantics, traceable)."""
        if not self._enable:
            return
        state = self._opt_states.get(id(optimizer), OptimizerState.INIT)
        if state is OptimizerState.UNSCALED:
            raise RuntimeError("unscale_() has already been called on this optimizer since the last update()")
        if state is OptimizerState.STEPPED:
            raise RuntimeError("unscale_() is being called after step()")

        params = self._params_with_grads(optimizer)
        inv_scale = (1.0 / self._scale)
        found = jnp.asarray(False)
        for p in params:
            g = p._grad._data
            if np.dtype(g.dtype).kind in "fc":
                found = found | ~jnp.all(jnp.isfinite(g))
                p._grad._data = (g.astype(jnp.float32) * inv_scale).astype(g.dtype)
        # OR, not overwrite: a second optimizer's unscale_ must not
        # erase the first's verdict
        self._found_inf = self._found_inf | found
        self._opt_states[id(optimizer)] = OptimizerState.UNSCALED

    # ------------------------------------------------------------------
    def _snapshot(self, optimizer):
        params = [p for p in optimizer._parameter_list if not p.stop_gradient]
        old_params = [p._data for p in params]
        old_accums = jax.tree_util.tree_map(lambda a: a, optimizer._accumulators)
        return params, old_params, old_accums

    def _rollback_where_inf(self, optimizer, params, old_params, old_accums, creation_log):
        found = self._found_inf
        for p, old in zip(params, old_params):
            if p._data is not old:
                p._data = jnp.where(found, old, p._data)
        for name, store in optimizer._accumulators.items():
            old_store = old_accums.get(name, {})
            for pname, arr in store.items():
                # accumulators created DURING the (possibly skipped) step
                # roll back to their creation-time init value
                old = old_store.get(pname, creation_log.get((name, pname)))
                if old is not None and old is not arr:
                    store[pname] = jnp.where(found, old, arr)

    def step(self, optimizer):
        """Unscale (if needed) then step, skipping the update when inf/nan
        grads were found (ref: grad_scaler.py step)."""
        if not self._enable:
            optimizer.step()
            return
        state = self._opt_states.get(id(optimizer), OptimizerState.INIT)
        if state is OptimizerState.STEPPED:
            raise RuntimeError("step() has already been called since the last update()")
        if state is OptimizerState.INIT:
            self.unscale_(optimizer)

        snap = self._snapshot(optimizer)
        optimizer._accum_creation_log = {}
        try:
            optimizer.step()
            self._rollback_where_inf(optimizer, *snap, optimizer._accum_creation_log)
        finally:
            optimizer._accum_creation_log = None
        self._opt_states[id(optimizer)] = OptimizerState.STEPPED

    def update(self):
        """Advance the dynamic loss scale (ref: grad_scaler.py update)."""
        if not self._enable:
            return
        if not isinstance(self._found_inf, jax.core.Tracer):
            # observable skips: a silently-dropped step is an anomaly
            # signal (the training supervisor's detector subscribes via
            # on_skip); counters only advance on concrete values so a
            # trace never leaks a tracer into host state
            step_ix = self._n_updates
            self._n_updates += 1
            if bool(np.asarray(self._found_inf)):
                self._n_skipped_steps += 1
                self._last_skip_step = step_ix
                if self._on_skip is not None:
                    self._on_skip(step_ix)
        if self._use_dynamic_loss_scaling:
            found = self._found_inf
            # consecutive counters: a good step resets bad and vice versa
            # (reference update_loss_scaling kernel semantics)
            bad = jnp.where(found, self._bad_steps + 1, 0)
            good = jnp.where(found, 0, self._good_steps + 1)
            # decrease after N consecutive bad steps
            shrink = bad >= self._decr_every_n_nan_or_inf
            scale = jnp.where(shrink, self._scale * self._decr_ratio, self._scale)
            bad = jnp.where(shrink, 0, bad)
            # increase after N consecutive good steps
            grow = good >= self._incr_every_n_steps
            scale = jnp.where(grow, scale * self._incr_ratio, scale)
            good = jnp.where(grow, 0, good)
            self._scale = jnp.maximum(scale, jnp.asarray(1.0, jnp.float32))
            self._good_steps = good
            self._bad_steps = bad
        self._found_inf = jnp.asarray(False)
        self._opt_states.clear()

    def minimize(self, optimizer, *args, **kwargs):
        """step + update in one call (ref: AmpScaler.minimize)."""
        if not self._enable:
            return optimizer.step()
        self.step(optimizer)
        self.update()

    # ------------------------------------------------------------------
    @property
    def n_skipped_steps(self) -> int:
        """How many update() cycles found inf/nan grads and skipped the
        optimizer step (eager path; see update() for the jit caveat)."""
        return self._n_skipped_steps

    @property
    def last_skip_step(self) -> int:
        """0-based update() index of the most recent skipped step, or
        -1 when no step has been skipped."""
        return self._last_skip_step

    def set_on_skip(self, callback) -> None:
        """Install/replace the on-skip observer: ``callback(step_ix)``
        fires at update() time for every skipped step."""
        self._on_skip = callback

    # ------------------------------------------------------------------
    def get_scale_value(self) -> float:
        return float(np.asarray(self._scale))

    def set_scale_value(self, value: float):
        self._scale = jnp.asarray(float(value), jnp.float32)

    # GradScaler-compat accessor names (ref: grad_scaler.py:617 section)
    def get_init_loss_scaling(self):
        return self._init_loss_scaling

    def set_init_loss_scaling(self, v):
        self._init_loss_scaling = float(v)
        self._scale = jnp.asarray(self._init_loss_scaling, jnp.float32)

    def get_incr_ratio(self):
        return self._incr_ratio

    def set_incr_ratio(self, v):
        if v <= 1.0:
            raise ValueError("incr_ratio should be > 1")
        self._incr_ratio = float(v)

    def get_decr_ratio(self):
        return self._decr_ratio

    def set_decr_ratio(self, v):
        if not 0.0 < v < 1.0:
            raise ValueError("decr_ratio should be in (0, 1)")
        self._decr_ratio = float(v)

    def get_incr_every_n_steps(self):
        return self._incr_every_n_steps

    def set_incr_every_n_steps(self, v):
        self._incr_every_n_steps = int(v)

    def get_decr_every_n_nan_or_inf(self):
        return self._decr_every_n_nan_or_inf

    def set_decr_every_n_nan_or_inf(self, v):
        self._decr_every_n_nan_or_inf = int(v)

    def state_dict(self):
        if not self._enable:
            return {}
        return {
            "scale": np.asarray(self._scale),
            "incr_ratio": self._incr_ratio,
            "decr_ratio": self._decr_ratio,
            "incr_every_n_steps": self._incr_every_n_steps,
            "decr_every_n_nan_or_inf": self._decr_every_n_nan_or_inf,
            "incr_count": int(np.asarray(self._good_steps)),
            "decr_count": int(np.asarray(self._bad_steps)),
            "use_dynamic_loss_scaling": self._use_dynamic_loss_scaling,
        }

    def load_state_dict(self, state_dict):
        if not self._enable:
            if state_dict:
                warnings.warn("Load state_dict on a disabled GradScaler: ignored")
            return
        self._scale = jnp.asarray(np.asarray(state_dict["scale"]).reshape(()), jnp.float32)
        self._incr_ratio = float(state_dict["incr_ratio"])
        self._decr_ratio = float(state_dict["decr_ratio"])
        self._incr_every_n_steps = int(state_dict["incr_every_n_steps"])
        self._decr_every_n_nan_or_inf = int(state_dict["decr_every_n_nan_or_inf"])
        self._good_steps = jnp.asarray(int(state_dict.get("incr_count", 0)), jnp.int32)
        self._bad_steps = jnp.asarray(int(state_dict.get("decr_count", 0)), jnp.int32)


class GradScaler(AmpScaler):
    """Public API name (ref: paddle.amp.GradScaler, grad_scaler.py:617)."""
