"""Host spans of the program on the profiler's trace.

``span(name)`` marks a stretch of host work as ``hermes.<name>``
(``jax.profiler.TraceAnnotation``).  With no trace being taken a span
costs about a microsecond, so the spans stay on: the refresh dispatch has
a handful against milliseconds of work.
"""
import jax

PREFIX = "hermes."


def span(name: str):
    return jax.profiler.TraceAnnotation(PREFIX + name)
