"""Profiler spans at the federation's layer boundaries.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: it records
only while a profiler trace runs (``jax.profiler.trace(dir)``, or
``python -m repro.launch.federate --profile DIR``) and costs under a
microsecond otherwise, so no flag turns it on. Every span is named
``repro.<step>``; its keyword args (family, rows, pool and bucket sizes)
are the step's counters and show as the event's stats in the trace.

``host_read(x, what)`` is the one way the hot path turns a device array
into a host array. Such a read waits for every queued program that
produces ``x``, so the device may drain its queue and idle until the
host dispatches again; each read gets a ``repro.host_sync`` span named
by ``what``. Transfers are allowed inside it, so a run under
``jax.transfer_guard_device_to_host("disallow")`` raises at any read that
bypasses it.
"""
from __future__ import annotations

import jax
import numpy as np


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span on the profiler's clock; ``name`` starts ``repro.``."""
    return jax.profiler.TraceAnnotation(name, **args)


def host_read(x, what: str, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)`` inside a ``repro.host_sync`` span."""
    with span("repro.host_sync", what=what), \
            jax.transfer_guard_device_to_host("allow"):
        return np.asarray(x, dtype)
