"""Named host spans of the serving and compile paths.

A span is a TraceMe event in the JAX profiler's own host plane, on the
clock the profiler aligns with the device planes, so a trace shows which
host step the chip waited on.  With no trace running a span costs one
TraceMe check.  The names are an interface: a trace reduction lists
them.

    with spans.span("serve.wave", n_active=12):
        ...

Callers look the function up on the module (``spans.span``), so a test
can swap in a recorder.
"""
from __future__ import annotations

import jax


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A ``with`` block recorded as ``name``; ``meta`` (scalars) becomes
    the event's stats in the trace."""
    return jax.profiler.TraceAnnotation(name, **meta)
