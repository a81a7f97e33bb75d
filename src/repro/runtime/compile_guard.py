"""Compile-counting guard: machine-checks the zero-retrace invariant.

The serving engine's whole latency story assumes the decode loop compiles
ZERO new XLA programs at steady state: every jitted program is built once
in `_EngineBase.__init__`, page tables mutate host-side values-only, and
admission/fold/deferral/preemption events reuse the warm programs.  None
of that is visible to a correctness test — a hidden retrace produces the
same tokens, just 100x slower.  This module makes it assertable:

    from repro.runtime import compile_guard

    eng.run()                                  # warmup: compiles everything
    with compile_guard.count_compiles() as log:
        ... steady-state serving traffic ...
    assert log.count == 0, log.describe()

Implementation: `jax.monitoring` fires a
``/jax/core/compile/backend_compile_duration`` event exactly once per
actual backend (XLA) compilation — jit-cache hits fire nothing (verified
against jax 0.9.0).  One process-wide listener is registered
lazily and fans out to the currently-active logs, so nested/overlapping
guards each see every compile in their window.  For human-readable
diagnostics the guard also flips ``jax_log_compiles`` inside the context
and captures the "Finished tracing + transforming <name> ..." log lines,
so a failing assertion names the offending program.

`tests/test_retrace.py` drives a live engine through admission, window
folds, deferral, and preempt+recompute under this guard for both the
mixed and paged backends.
"""

from __future__ import annotations

import contextlib
import logging
import threading
from typing import Iterator, List, Set

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# jax emits per-program trace/lower/compile messages on these
# loggers when jax_log_compiles is on
_LOG_SOURCES = ("jax._src.dispatch", "jax._src.interpreters.pxla",
                "jax._src.pjit")

_lock = threading.Lock()
_active: Set["CompileLog"] = set()
_listener_installed = False


class CompileLog:
    """Compilations observed while a `count_compiles()` context is open."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0               # backend compile time, summed
        self.names: List[str] = []       # best-effort program names

    def describe(self) -> str:
        if self.count == 0:
            return "0 compilations"
        names = ", ".join(self.names) if self.names else "names unavailable"
        return (f"{self.count} XLA compilation(s) inside the guarded "
                f"region ({names}) — a jitted program retraced; the decode "
                "loop must reuse the programs built at engine setup")


def _on_event(event: str, duration: float = 0.0, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    with _lock:
        for log in _active:
            log.count += 1
            log.seconds += duration


def _ensure_listener() -> None:
    """Register the process-wide monitoring listener once.  It stays
    installed and fans out to whatever logs are active (none, outside any
    guard), so overlapping guards never race an unregister."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listener_installed = True


class _NameCapture(logging.Handler):
    def __init__(self, log: CompileLog) -> None:
        super().__init__(level=logging.DEBUG)
        self._log = log

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if "Finished tracing + transforming" in msg \
                or "Compiling" in msg:
            with _lock:
                self._log.names.append(msg.split(" for ")[0].strip())


@contextlib.contextmanager
def count_compiles() -> Iterator[CompileLog]:
    """Count actual XLA compilations in the enclosed region (0 == every
    jitted call was a cache hit).  Reentrant; thread-safe; counts compiles
    from ALL threads (jit caches are process-global, so that is the
    invariant worth holding)."""
    _ensure_listener()
    log = CompileLog()
    prev = jax.config.jax_log_compiles
    handlers = []
    with _lock:
        _active.add(log)
    try:
        jax.config.update("jax_log_compiles", True)
        for name in _LOG_SOURCES:
            lg = logging.getLogger(name)
            h = _NameCapture(log)
            lg.addHandler(h)
            handlers.append((lg, h))
        yield log
    finally:
        for lg, h in handlers:
            lg.removeHandler(h)
        jax.config.update("jax_log_compiles", prev)
        with _lock:
            _active.discard(log)


class RetraceError(AssertionError):
    """A guarded region compiled new XLA programs."""


@contextlib.contextmanager
def assert_no_compiles() -> Iterator[CompileLog]:
    """Hard-assert flavor: raises `RetraceError` (with program names when
    available) if anything compiled inside the region."""
    with count_compiles() as log:
        yield log
    if log.count:
        raise RetraceError(log.describe())
