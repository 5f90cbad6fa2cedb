"""Multi-controller-safe placement helper."""
from __future__ import annotations

import jax


def global_device_put(value, sharding):
    """Place host/process-local ``value`` with ``sharding``, safely in
    multi-controller mode.

    ``jax.device_put`` onto a sharding with non-addressable devices
    first runs ``multihost_utils.assert_equal`` — a cross-process
    broadcast per call. Besides the per-array sync cost, interleaving
    many of those small gloo broadcasts has been observed to desync the
    transport (``op.preamble.length <= op.nbytes`` aborts) on the CPU
    backend. ``make_array_from_process_local_data`` builds the same
    global array purely from each process's addressable shards — no
    collective at all — so placement loops (parameter sharding, stacked
    pipeline stages, optimizer state) go through here. Single-process
    (or fully-addressable target) falls back to plain device_put.
    """
    import numpy as np

    if jax.process_count() > 1 and not sharding.is_fully_addressable:
        host = np.asarray(value)
        return jax.make_array_from_process_local_data(
            sharding, host, host.shape)
    return jax.device_put(value, sharding)
