"""Host spans of the program.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: outside a profiler session it records nothing, and inside
one it lands on the same clock as the device's events, so a reduction of the
trace can say which host phase the device waited on.  Keyword arguments
become the span's arguments (``round=3``).

Device programs name their phases with ``jax.named_scope`` where they are
written (``federated/client.py``); those names reach the compiled program's
``op_name`` metadata and change nothing else in it.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **args):
    """A host span ``repro.<name>``, used as a context manager."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
