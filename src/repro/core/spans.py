"""Names of the host spans ``run_gpic`` records, and the span itself.

A span is a ``jax.profiler.TraceAnnotation``: with no profiler running it
records nothing, so the spans stay in the code always. Under
``jax.profiler.start_trace`` each lands on the host plane of the trace,
nested in time on the calling thread:

    gpic.run          the whole call (metadata ``call``: the call's number
                      in this process, shared by the spans of one job)
      gpic.front_door config checks, ``validate_features``, ``row_reorder``
      gpic.launch     the enqueue of the jitted program (a retrace or
                      recompile shows up here as a long span)
      gpic.health     fallback bookkeeping and ``raise_for_health``
    gpic.sync         one blocking device-to-host read, wherever it happens:
                      counting the spans counts the syncs

Readers of a trace import the names from here, so a rename moves the
program and its readers together.
"""
from __future__ import annotations

import jax

RUN = "gpic.run"
FRONT_DOOR = "gpic.front_door"
LAUNCH = "gpic.launch"
HEALTH = "gpic.health"
SYNC = "gpic.sync"


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span called ``name`` with ``meta`` as its metadata."""
    return jax.profiler.TraceAnnotation(name, **meta)
