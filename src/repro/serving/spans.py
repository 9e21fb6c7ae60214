"""The serving engine's spans in the JAX profiler's trace.

Under a profiler session (``jax.profiler.trace(dir)``) each span is a
``jax.profiler.TraceAnnotation`` on the calling thread: it lands on the
host plane of the ``.xplane.pb``, on the same clock as the device's
``XLA Ops``, and nests by time.  With no session collecting, a span is one
shared no-op context, so a call pays one ``is_enabled()`` check, taken by
:func:`tracer` at its entry, and builds nothing.
"""

from __future__ import annotations

import contextlib
from typing import Callable, ContextManager

from jax.profiler import TraceAnnotation

_NO_SPAN = contextlib.nullcontext()


def _no_span(name: str) -> ContextManager:
    return _NO_SPAN


def tracer() -> Callable[[str], ContextManager]:
    """The span factory for one engine call: ``TraceAnnotation`` while a
    profiler session collects, else a factory of the shared no-op."""
    return TraceAnnotation if TraceAnnotation.is_enabled() else _no_span
