"""Host spans of the serving program, written into the JAX profiler's trace.

    from repro.runtime import tracing

    with tracing.span("engine.decode"):
        ...
    with tracing.span("engine.prefill", request_id=rid, prompt_tokens=n):
        ...

`span` is a `jax.profiler.TraceAnnotation`: with no profiler session
active it records nothing and costs 0.5-1.5 us on a TPU v5e host, the
more the more stats it carries (PERF.md §5), so it is used as is, with
no switch of its own.  Under a session (`jax.profiler.start_trace`, or a
profile captured from a running server) the span lands in the same
`.xplane.pb` as the device's ops, on the same clock, with its `meta` as
the event's stats.  Spans that belong to one request carry its id as
`request_id`.

A span opens and closes on one thread with no `await` between: spans of
coroutines that interleave on one event loop would not nest.

`install_gc_span()` adds a `host.gc` span (stat `generation`) around every
collection of Python's garbage collector, so a host stall that is a
collection is named in the trace.  It is process-wide and idempotent.
"""

from __future__ import annotations

import gc

import jax


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span named `name` with `meta` as its stats."""
    return jax.profiler.TraceAnnotation(name, **meta)


class _GcSpan:
    """gc callback: opens `host.gc` at a collection's start and closes it at
    its stop.  Collections never overlap, so one open span suffices."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if jax.profiler.TraceAnnotation.is_enabled():
                self._open = span("host.gc", generation=info["generation"])
                self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


_GC_SPAN = _GcSpan()


def install_gc_span() -> None:
    """Register the `host.gc` span with `gc.callbacks`, once per process."""
    if _GC_SPAN not in gc.callbacks:
        gc.callbacks.append(_GC_SPAN)
