"""Host spans on the profiler's clock.

``with span("store.fetch"):`` opens ``jax.profiler.TraceAnnotation(
"repro.store.fetch")``: an event on the calling thread's line of the
profile's host plane, on the same clock as the device's ops, recorded
only while a profiler session runs (``jax.profiler.start_trace``).
Keyword arguments become the event's stats; give only values the host
already holds (counts, ids, shapes), never a device array, whose value
would make the span wait for the device.

A process that has not imported jax has no profiler session to record
into, so there a span is a shared no-op context, and modules that must
stay numpy-only (``repro.store``, ``repro.pipeline.generate``, which
generation workers import on a spawn-time budget) can open spans
without pulling jax in.
"""
from __future__ import annotations

import contextlib
import sys

PREFIX = "repro."
_NULL = contextlib.nullcontext()


def span(name: str, **args):
    """A host span named ``repro.<name>`` carrying ``args``."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
