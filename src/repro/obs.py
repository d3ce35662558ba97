"""Spans of the program's own layers, on the JAX profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: a host
event that lands in the same profiler session as the device planes, so
a trace shows what the program was doing around every device op and
every idle gap.  With no profiler running it costs about a microsecond.

Importing the store does not import JAX, and a span must not either:
while ``jax`` is not loaded no profiler can be running, so ``span``
returns a shared null context instead.

Names are ``<layer>.<what>`` (``osd.verify``, ``loader.produce``); a
span nests under the span its thread has open.  ``meta`` is kept for a
request identifier (``req=``, ``step=``) that ties spans of one request
or batch together across threads, or for a size known only once the
body has run: ``with span(...) as s: ...; s.set_metadata(bytes=n)``.
"""

from __future__ import annotations

import contextlib
import sys


class _NullSpan(contextlib.nullcontext):
    """The span while no profiler can run: enters as itself and drops
    metadata."""

    def __init__(self):
        super().__init__(self)

    def set_metadata(self, **meta) -> None:
        pass


_NULL = _NullSpan()


def span(name: str, **meta):
    """A context manager that records ``name`` (with ``meta``) as a
    profiler span while its body runs; it enters as an object whose
    ``set_metadata(**meta)`` adds metadata before the span closes."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name, **meta)
