"""Named host spans for profiling the serving and solver loops.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation`` named
``"repro." + name``. Under ``jax.profiler.trace`` (or TensorBoard's
profiler) each span shows as a host event on the same clock as the
device's ops, so the device's idle gaps can be attributed to what the
host was doing in them: fetching logits, sampling, admitting a request,
reading iterates back. When no profiler runs a span costs about a
microsecond, so the spans are always in the code; there is no switch.

The spans, and what each covers, are listed in docs/serving.md and
docs/solvers.md ("Profiling").
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **meta):
    """A profiler span ``repro.<name>``; ``meta`` is attached to the event."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)
