"""RNG state management.

TPU-native design: the reference keeps per-device Philox ``Generator``
states (ref: paddle/phi/core/generator.h) and, for model parallelism, a
named-seed ``RNGStatesTracker`` (ref:
python/paddle/distributed/fleet/layers/mpu/random.py:34) so dropout differs
across TP ranks but matches across DP ranks.

Here a ``Generator`` owns a JAX PRNG key that is *split* on every draw.
Because jax arrays are immutable the state is a value, which makes the
generator safe both eagerly and inside a jit trace: the functionalized
train step (paddle_tpu.jit) threads the key through the step state, so
compiled steps get fresh randomness each call, exactly like the
reference's stateful Philox offset.
"""
from __future__ import annotations

import contextlib
import zlib
from typing import Dict

import jax
import numpy as np


class Generator:
    """Splittable PRNG state (Philox-state parity: seed + evolving key)."""

    def __init__(self, seed: int = 0):
        self._seed = int(seed)
        # key creation is LAZY: building a jax PRNG key initializes the
        # XLA backend, and this module is imported by `import paddle_tpu`
        # — which must stay backend-free so multi-controller workers can
        # call jax.distributed.initialize after import
        # (multi_controller.initialize_from_env)
        self._key = None

    @property
    def _k(self):
        if self._key is None:
            self._key = jax.random.key(self._seed)
        return self._key

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        # stay lazy: paddle.seed() at the top of a multi-controller
        # worker must not initialize the backend before
        # jax.distributed.initialize (same invariant as __init__)
        self._key = None
        return self

    def initial_seed(self) -> int:
        return self._seed

    # -- state (for checkpoint / tracker swap) ----------------------------
    def get_state(self):
        return self._k

    def set_state(self, state):
        self._key = state

    # -- drawing ----------------------------------------------------------
    def split(self):
        """Return a fresh subkey, advancing the generator state."""
        self._key, sub = jax.random.split(self._k)
        return sub

    def split_n(self, n: int):
        keys = jax.random.split(self._k, n + 1)
        self._key = keys[0]
        return keys[1:]


_default_generator = Generator(np.random.randint(0, 2**31 - 1))


def default_generator() -> Generator:
    return _default_generator


def seed(value: int) -> Generator:
    """paddle.seed parity (ref: python/paddle/framework/random.py)."""
    _default_generator.manual_seed(value)
    _tracker.reset()
    return _default_generator


def get_rng_state():
    return {"default": _default_generator.get_state(), "tracker": _tracker.get_states_dict()}


def set_rng_state(state):
    _default_generator.set_state(state["default"])
    _tracker.set_states_dict(state["tracker"])


def next_key():
    """Fresh subkey from the default generator (internal op plumbing)."""
    return _default_generator.split()


# -- checkpointable RNG state ------------------------------------------------
# jax typed PRNG keys (key<fry> dtype) cannot pass through np.asarray, so
# checkpoint writers (AutoCheckpoint, the training supervisor's peer
# snapshots) lower them to plain uint32 arrays first. The tag dict keeps
# the encoded form self-describing inside a pickled state tree.
_KEY_TAG = "__paddle_tpu_prng_key__"


def _encode_key(key):
    if isinstance(key, dict) and key.get(_KEY_TAG) == 1:
        return key  # already encoded (encoding is idempotent)
    return {_KEY_TAG: 1,
            "data": np.asarray(jax.device_get(jax.random.key_data(key)))}


def _decode_key(enc):
    if not (isinstance(enc, dict) and enc.get(_KEY_TAG) == 1):
        return enc  # already a live key (in-memory snapshot path)
    return jax.random.wrap_key_data(jax.numpy.asarray(enc["data"]))


def encode_rng_state(state):
    """Lower a :func:`get_rng_state`-shaped dict's PRNG keys to plain
    numpy payloads — safe to pickle/``framework.io.save`` and to ship
    across processes (peer-replicated snapshots)."""
    return {
        "default": _encode_key(state["default"]),
        "tracker": {k: _encode_key(v)
                    for k, v in state["tracker"].items()},
    }


def serializable_rng_state():
    """:func:`encode_rng_state` of the CURRENT global RNG state."""
    return encode_rng_state(get_rng_state())


def restore_rng_state(state):
    """Inverse of :func:`serializable_rng_state`; also accepts a live
    :func:`get_rng_state` dict (keys pass through untouched)."""
    set_rng_state({
        "default": _decode_key(state["default"]),
        "tracker": {k: _decode_key(v)
                    for k, v in state["tracker"].items()},
    })


class RNGStatesTracker:
    """Named RNG branches for hybrid parallelism.

    ref: fleet/layers/mpu/random.py:34 — `global_seed` shared across all
    ranks, `local_seed` unique per TP rank so dropout masks decorrelate
    inside a tensor-parallel group while weights stay identical.
    """

    GLOBAL = "global_seed"
    LOCAL = "local_seed"

    def __init__(self):
        self._states: Dict[str, Generator] = {}

    def reset(self):
        self._states.clear()

    def add(self, name: str, seed: int):
        if name in self._states:
            raise ValueError(f"rng state {name!r} already exists")
        self._states[name] = Generator(seed)

    def exists(self, name: str) -> bool:
        return name in self._states

    @contextlib.contextmanager
    def rng_state(self, name: str = GLOBAL):
        """Swap the default generator for the named branch inside the ctx."""
        global _default_generator
        if name not in self._states:
            # lazily branch off the default seed, folding in a deterministic
            # digest of the name — hash() is randomized per process
            # (PYTHONHASHSEED) and would silently desynchronize the
            # documented cross-rank invariant of the global branch
            self._states[name] = Generator(
                (_default_generator.initial_seed() + zlib.crc32(name.encode()))
                % 2**31
            )
        prev = _default_generator
        _default_generator = self._states[name]
        try:
            yield
        finally:
            _default_generator = prev

    def get_states_dict(self):
        return {k: g.get_state() for k, g in self._states.items()}

    def set_states_dict(self, states):
        for k, v in states.items():
            if k not in self._states:
                self._states[k] = Generator(0)
            self._states[k].set_state(v)


_tracker = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _tracker


def model_parallel_random_seed(seed_: int, tp_rank: int = 0):
    """ref: fleet/layers/mpu/random.py:103 — seed global branch identically
    on every rank, local branch offset by TP rank."""
    _tracker.reset()
    _tracker.add(RNGStatesTracker.GLOBAL, seed_)
    _tracker.add(RNGStatesTracker.LOCAL, seed_ + 2718 + tp_rank)
