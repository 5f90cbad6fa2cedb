"""Device/compile timeline hooks: XLA compile events on the obs
timeline and in the registry.

Reuses the exact jax compile-log seam ``recompile_guard`` listens on
(``analysis/sanitizers.py``: the ``Compiling jit(<name>) ...`` records
from ``jax._src.interpreters.pxla``) plus the ``Finished XLA
compilation of jit(<name>) in <t> sec`` record ``jax._src.dispatch``
emits, so compile COUNT and WALL TIME are both captured, tagged by the
bare program name, with no private jax API touched.
If the logging shape ever changes, counts drop to zero and the pinned
obs tests fail visibly — the same failure contract the guard makes.

Install is explicit and idempotent (:func:`install_compile_events`);
:func:`uninstall_compile_events` restores the loggers exactly, so the
hook composes with ``recompile_guard`` (which snapshots and restores
logger state around its own handler) and never leaks DEBUG levels
into an application's root logging.
"""
from __future__ import annotations

import logging
import re
from typing import List, Optional, Tuple

from ..analysis.sanitizers import (
    COMPILE_LOGGERS,
    COMPILING_RE,
    program_name,
)
from .metrics import registry
from .trace import instant

__all__ = [
    "install_compile_events",
    "uninstall_compile_events",
    "compile_events_installed",
]

# the wall-time record comes from the dispatch logger (see
# jax._src.dispatch.log_elapsed_time), not the compile logger
FINISHED_LOGGER = "jax._src.dispatch"
FINISHED_RE = re.compile(
    r"Finished XLA compilation of (\S+) in ([0-9.eE+-]+) sec")

_ALL_LOGGERS: Tuple[str, ...] = tuple(COMPILE_LOGGERS) + (
    FINISHED_LOGGER,)


class _CompileHandler(logging.Handler):
    """Parses the two record shapes into registry series + timeline
    instants. Counter: ``jax_compiles_total{program}``. Histogram:
    ``jax_compile_seconds{program}``."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)

    def emit(self, record):
        try:
            msg = record.getMessage()
        except Exception:  # noqa: BLE001 — logging must never raise
            return
        try:
            m = COMPILING_RE.search(msg)
            if m:
                name = program_name(m.group(1))
                registry().counter(
                    "jax_compiles_total", {"program": name},
                    help="XLA compilations by program name").inc()
                instant("xla_compile", tid="compile", program=name)
                return
            m = FINISHED_RE.search(msg)
            if m:
                name, secs = program_name(m.group(1)), float(m.group(2))
                registry().histogram(
                    "jax_compile_seconds", {"program": name},
                    help="XLA compile wall time by program"
                ).observe(secs)
                instant("xla_compile_done", tid="compile",
                        program=name, seconds=secs)
        except Exception:  # noqa: BLE001 — logging must never raise
            pass


_installed: Optional[_CompileHandler] = None
_saved: List[Tuple[logging.Logger, int, bool]] = []


def compile_events_installed() -> bool:
    return _installed is not None


def install_compile_events() -> None:
    """Attach the compile-event handler (idempotent). Lowers only the
    jax compile and dispatch loggers to DEBUG and stops their
    propagation (the guard's exact discipline) so the temporarily-
    DEBUG records don't spray through the application's root
    handler."""
    global _installed
    if _installed is not None:
        return
    handler = _CompileHandler()
    for name in _ALL_LOGGERS:
        lg = logging.getLogger(name)
        _saved.append((lg, lg.level, lg.propagate))
        if lg.getEffectiveLevel() > logging.DEBUG:
            lg.setLevel(logging.DEBUG)
            lg.propagate = False
        lg.addHandler(handler)
    _installed = handler


def uninstall_compile_events() -> None:
    """Detach and restore every logger exactly (level + propagate)."""
    global _installed
    if _installed is None:
        return
    for lg, lvl, prop in _saved:
        try:
            lg.removeHandler(_installed)
            lg.setLevel(lvl)
            lg.propagate = prop
        except Exception:  # noqa: BLE001 — restore the rest anyway
            pass
    _saved.clear()
    _installed = None
