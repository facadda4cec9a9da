"""Spans of the served path on the profiler's clock.

``span(name, **counters)`` opens a ``jax.profiler.TraceAnnotation`` named
``biathlon.<name>``.  A running profiler (``jax.profiler.trace(dir)``)
records it on the host's timeline beside the device ops, with each counter
as an integer stat of the event; with no profiler running it costs about a
microsecond, so the served path emits its spans always.  A counter known
only at the end of the span is added with ``set_metadata`` on the object
the ``with`` statement binds.

Spans wrap work that is already there: none blocks on the device or copies
a buffer, and counters come from ``.nbytes`` and shapes.
"""
from __future__ import annotations

import jax

PREFIX = "biathlon."


def span(name: str, **counters: int) -> jax.profiler.TraceAnnotation:
    """The trace annotation ``biathlon.<name>`` carrying ``counters``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counters)
