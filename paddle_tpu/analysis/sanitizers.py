"""Runtime sanitizers — the dynamic companion to the static rules.

:func:`recompile_guard` counts XLA compilations inside a ``with``
block and fails when the budget is exceeded. It is the runtime proof
behind RECOMP001: a serving/decode path that is SUPPOSED to compile
one program per (chunk width, decode shape) can silently start
recompiling per step after an innocent-looking change (a Python scalar
leaking into the traced signature, a shape that stopped being padded);
latency then quietly 10x's. Tests pin the expected compile count so
the regression fails loudly instead.

:func:`collective_contract` is the dynamic companion to COLL002/
COLL003: each rank's eager collectives append signatures to the
collective flight recorder
(``distributed/communication/flight_recorder.py``); the contract
cross-checks all ranks' recorded schedules through a shared KV store
and raises :class:`CollectiveScheduleMismatch` — naming every rank's
last-N schedule — when they diverge. What the static rules prove
impossible on the analyzable call graph, the contract catches at test
time, and the CommWatchdog dumps at hang time.

The lock-order sanitizer (graft-race's runtime half, the dynamic
companion to RACE001/LOCK001/LOCK002) lives in
``paddle_tpu/utils/locks.py`` and is RE-EXPORTED here lazily:
:class:`TracedLock` records per-thread held-lock sets and acquisition
sites, maintains the runtime lock-order graph, and raises
:class:`LockOrderViolation` naming both stacks the moment two locks
are taken in inverted order; :func:`instrument_locks` patches the
``threading.Lock``/``RLock`` factories so a whole process runs under
it, and a ``flight_recorder.register_dump_extra`` hook renders every
thread's held locks into CommWatchdog/supervisor hang dumps.

The resource-leak sanitizer (graft-own's runtime half, the dynamic
companion to OWN001/OWN002/OWN003) lives in
``paddle_tpu/utils/resources.py`` and is RE-EXPORTED here the same
way: :class:`ResourceLedger` mirrors every KV-block / engine-slot /
handoff-hold acquire+release with its acquisition site,
:meth:`~ResourceLedger.verify` asserts block conservation against a
live ``BlockManager``, and :meth:`~ResourceLedger.leak_check` raises
:class:`ResourceLeakError` naming where every outstanding resource
was taken; :func:`instrument_resources` wraps the ``BlockManager``
reference primitives so a whole process runs under it
(``PADDLE_LEAK_SANITIZER=1`` in the 2-process serving proofs).

Implementation: jax logs one "Compiling jit(<name>) with global shapes
and types (...)" record per lowering it hands to XLA (logger
``jax._src.interpreters.pxla``, DEBUG level unless jax_log_compiles is
set; a persistent-cache hit still logs it, so "0 compilations" means no
retrace, not merely no backend work). The guard attaches a logging
handler, parses those records into :class:`CompileEvent`s with the
``jit(...)`` wrapper stripped, and checks the count on exit. No private
jax API is touched. If the logging shape changes again the guard counts
0 — which is why every ``max_compiles=0`` pin is paired with a warm-up
run asserting an EXACT non-zero count: the pair fails loudly instead of
passing vacuously.
"""
from __future__ import annotations

import contextlib
import logging
import re
import threading
from dataclasses import dataclass
from typing import List, Optional

__all__ = ["CompileEvent", "RecompileError", "RecompileGuard",
           "recompile_guard", "CollectiveScheduleMismatch",
           "collective_contract", "COMPILE_LOGGERS", "COMPILING_RE",
           "program_name",
           "LockOrderViolation", "TracedLock", "instrument_locks",
           "uninstrument_locks", "ResourceLeakError", "ResourceLedger",
           "instrument_resources", "uninstrument_resources"]

_LOCK_SANITIZER_API = ("LockOrderViolation", "TracedLock",
                       "instrument_locks", "uninstrument_locks")
_LEAK_SANITIZER_API = ("ResourceLeakError", "ResourceLedger",
                       "instrument_resources", "uninstrument_resources")


def __getattr__(name: str):
    # the runtime sanitizers live in utils/ (stdlib-only, usable
    # without the analysis package); re-exported lazily so importing
    # the analyzer never drags paddle_tpu.utils in, and vice versa
    if name in _LOCK_SANITIZER_API:
        from ..utils import locks as _locks

        return getattr(_locks, name)
    if name in _LEAK_SANITIZER_API:
        from ..utils import resources as _resources

        return getattr(_resources, name)
    raise AttributeError(name)


class CollectiveScheduleMismatch(AssertionError):
    """Two ranks recorded different collective schedules — the
    runtime-confirmed COLL002 deadlock shape. The message names every
    rank's last-N recorded schedule and the first diverging entry."""


def collective_contract(store, rank, world_size, *, last_n=32,
                        deadline=None, recorder=None, tag="default"):
    """Cross-check the collective flight recorder's schedule against
    every peer through ``store`` (TCPKVStore/FileKVStore). Raises
    :class:`CollectiveScheduleMismatch` on divergence; returns the
    per-rank schedules (``{rank: [CollectiveSignature, ...]}``) on
    agreement. Every rank must call it the same number of times — the
    contract is itself a synchronization point. See
    ``distributed/communication/flight_recorder.py`` for the recording
    side; ``deadline`` (seconds or a ``utils.retries.Deadline``)
    bounds the wait for peers' schedules (default 30 s)."""
    from ..distributed.communication import flight_recorder as _fr

    return _fr.contract(store, rank, world_size, last_n=last_n,
                        deadline=deadline, recorder_=recorder, tag=tag)

# The guard's own seam: jax's compile log. (The obs timeline's compile
# spans come from jax.monitoring instead: paddle_tpu/obs/compile.py.)
COMPILE_LOGGERS = ("jax._src.interpreters.pxla",)
COMPILING_RE = re.compile(
    r"Compiling (\S+) with global shapes and types (.+?)"
    r"(?:\. Argument mapping.*)?$")
_WRAPPED_NAME_RE = re.compile(r"^\w+\((.+)\)$")


def program_name(module_name: str) -> str:
    """The bare function name from jax's module name: ``jit(prefill)``
    -> ``prefill`` (a name that is not wrapped comes back unchanged)."""
    m = _WRAPPED_NAME_RE.match(module_name)
    return m.group(1) if m else module_name


class RecompileError(AssertionError):
    """The guarded block compiled more XLA programs than budgeted."""


@dataclass(frozen=True)
class CompileEvent:
    name: str      # the jitted function's bare name (no ``jit(...)``)
    shapes: str    # "(ShapedArray(int32[2,8]), ...)" — the arg shapes
    message: str   # full log record, for diagnostics

    def __str__(self):
        return f"{self.name} {self.shapes}"


class RecompileGuard:
    """Collects CompileEvents; ``count()``/``events()`` filter by the
    compiled function name (regex search)."""

    def __init__(self, match: Optional[str] = None):
        self._match = match
        self._events: List[CompileEvent] = []
        self._lock = threading.Lock()

    def _record(self, message: str):
        m = COMPILING_RE.search(message)
        if not m:
            return
        ev = CompileEvent(program_name(m.group(1)), m.group(2), message)
        with self._lock:
            self._events.append(ev)

    def events(self, match: Optional[str] = None) -> List[CompileEvent]:
        pat = match if match is not None else self._match
        with self._lock:
            evs = list(self._events)
        if pat is None:
            return evs
        rx = re.compile(pat)
        return [e for e in evs if rx.search(e.name)]

    def count(self, match: Optional[str] = None) -> int:
        return len(self.events(match))

    def names(self, match: Optional[str] = None) -> List[str]:
        return [e.name for e in self.events(match)]


class _GuardHandler(logging.Handler):
    def __init__(self, guard: RecompileGuard):
        super().__init__(level=logging.DEBUG)
        self._guard = guard

    def emit(self, record):
        try:
            self._guard._record(record.getMessage())
        except Exception:  # noqa: BLE001 — logging must never raise
            pass


@contextlib.contextmanager
def recompile_guard(max_compiles: Optional[int] = None,
                    match: Optional[str] = None):
    """Count XLA compilations in the block; raise :class:`RecompileError`
    when more than ``max_compiles`` programs (whose names match
    ``match``, a regex, when given) were compiled.

    ``max_compiles=None`` only observes — read ``guard.count()`` /
    ``guard.events()`` afterwards. ``max_compiles=0`` asserts the block
    runs entirely on cached programs (the "warmed up, no silent
    retrace" pin)::

        with recompile_guard(match=r"prefill|decode") as g:
            engine.run()            # warm-up: compiles the programs
        assert g.count() == 2
        with recompile_guard(max_compiles=0, match=r"prefill|decode"):
            engine.run()            # steady state: cache hits only

    Guards nest; each sees every compilation inside its own block.
    """
    guard = RecompileGuard(match)
    handler = _GuardHandler(guard)
    loggers = [logging.getLogger(n) for n in COMPILE_LOGGERS]
    saved = [(lg, lg.level, lg.propagate) for lg in loggers]
    for lg in loggers:
        # the compile records are DEBUG unless jax_log_compiles is on;
        # lower only the compile logger, never the root — and stop
        # propagation so the temporarily-DEBUG records don't spray
        # through the application's root handler while the guard runs
        if lg.getEffectiveLevel() > logging.DEBUG:
            lg.setLevel(logging.DEBUG)
            lg.propagate = False
        lg.addHandler(handler)
    try:
        yield guard
    finally:
        # runs on EVERY exit — including an exception raised inside the
        # guarded block — and restores each logger independently, so a
        # failing guarded test can never leak the handler (or the
        # DEBUG level) into later tests
        for lg, lvl, prop in saved:
            try:
                lg.removeHandler(handler)
                lg.setLevel(lvl)
                lg.propagate = prop
            except Exception:  # noqa: BLE001 — restore the rest anyway
                pass
    if max_compiles is not None and guard.count() > max_compiles:
        evs = "\n  ".join(str(e) for e in guard.events())
        raise RecompileError(
            f"recompile_guard: {guard.count()} XLA compilation(s) in a "
            f"block budgeted for {max_compiles}"
            + (f" (match={match!r})" if match else "")
            + f":\n  {evs}")
