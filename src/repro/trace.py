"""Host spans of the served path, recorded by the JAX profiler.

``span(name, **args)`` names a phase of the serving loop (a pass, its
operand packing and staging, each chunk batch's read, staging and step,
the copy-back, the delivery) on the host timeline of a profiler trace, on
the same clock as the device planes.  The profiler is the only recorder:
a span costs the construction of one annotation when no trace runs, and
records nothing then.  Per-batch spans take no ``args``.
"""
from __future__ import annotations

import jax

PREFIX = "sem."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Context manager marking ``PREFIX + name`` on the host timeline;
    ``args`` become the event's stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
