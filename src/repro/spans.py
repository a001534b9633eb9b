"""Host spans in the profiler's trace.

``span("serve.decode", step=12)`` is a ``jax.profiler.TraceAnnotation``
named ``repro.serve.decode`` whose keyword arguments become the event's
stats.  It records only while a profiler session runs (``jax.profiler.
start_trace`` / ``trace``); otherwise it costs one inert object.  The
spans nest on the thread that opens them, and a trace puts them on the
same host clock as the device events, so every gap in the device's work
can be put down to the phase the host was in.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` with ``args`` as its stats; more can be
    added before it closes with ``set_metadata``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
