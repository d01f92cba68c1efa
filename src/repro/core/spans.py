"""Program spans: named host intervals on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``.  While a
profiler trace runs, each span lands in it beside the device's ops, with
``meta`` as the event's stats; with no trace running it costs about a
microsecond and records nothing.  The trace is the only record: nothing
is kept in memory, and there is no switch.

Every program span is named ``rt.<layer>...``.  A span only marks an
interval of the host thread that opens it: it never waits for the device
and copies nothing.  JAX is imported when a span first opens, so the DMS
server processes, which open none, never import it.
"""
from __future__ import annotations


def span(name: str, **meta):
    """A context manager marking ``name`` on this thread; the object it
    yields takes ``set_metadata(**meta)`` for what is known only inside."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **meta)
