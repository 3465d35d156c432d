"""Host spans of the served step, on the profiler's own clock.

Each span is a ``jax.profiler.TraceAnnotation`` named ``serve.<name>``: it
lands in the profiler's host plane beside the device ops of the same
trace, so a trace reader can say which part of the engine step the host
was in while the device sat idle. With no profiler session open a span
costs about a microsecond. Arguments known only at a span's end are added
with ``set_metadata``.
"""
from __future__ import annotations

import jax

PREFIX = "serve."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The span ``serve.<name>``, with ``args`` as its arguments."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
