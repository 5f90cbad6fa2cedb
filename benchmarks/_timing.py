"""Shared K-differencing step timer for the benchmark scripts.

One dispatch runs K scanned train steps; differencing two run lengths
cancels the constant host dispatch+fetch cost:
    per_step = (T(k2) - T(k1)) / (k2 - k1)
Used by bench.py-style scripts.
"""
import time

import numpy as np


def diff_time_ms(compiled, ids, labels, steps, k1=2, repeats=3):
    """Best-of-N per-step milliseconds for a jit.to_static function
    (already called once so optimizer state exists)."""
    if steps <= k1:
        raise ValueError(
            f"steps ({steps}) must exceed the short run k1 ({k1}) — "
            "the differencing denominator is steps - k1")
    np.asarray(compiled.multi_step(ids, labels, steps=k1)._data)
    np.asarray(compiled.multi_step(ids, labels, steps=steps)._data)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        np.asarray(compiled.multi_step(ids, labels, steps=steps)._data)
        t2 = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(compiled.multi_step(ids, labels, steps=k1)._data)
        t1 = time.perf_counter() - t0
        best = min(best, (t2 - t1) / (steps - k1))
    return best * 1e3
