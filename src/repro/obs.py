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
or batch together across threads.
"""

from __future__ import annotations

import contextlib
import sys

_NULL = contextlib.nullcontext()


def span(name: str, **meta):
    """A context manager that records ``name`` (with ``meta``) as a
    profiler span while its body runs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name, **meta)
