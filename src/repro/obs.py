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

``Counters`` keeps what the program counts as device arrays, summed on
the device as steps add to them, so counting makes no host read; ``read``
reads them all at once, where the caller chooses (after a measured
window, say).
"""
from __future__ import annotations

from typing import Dict

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


class Counters:
    """Named device-side sums."""

    def __init__(self):
        self._sums: Dict[str, jax.Array] = {}

    def add(self, name: str, value) -> None:
        prev = self._sums.get(name)
        self._sums[name] = value if prev is None else prev + value

    def reset(self) -> None:
        self._sums.clear()

    def read(self) -> Dict[str, np.ndarray]:
        """Every counter as a host array, in one ``repro.host_sync``."""
        with span("repro.host_sync", what="counters"), \
                jax.transfer_guard_device_to_host("allow"):
            return {k: np.asarray(v) for k, v in self._sums.items()}
