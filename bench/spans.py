"""The program's own ``repro.*`` spans in the trace the harness has just
written, reduced inside the ``bench.window`` span for the per-layer
metrics that read them.

The program opens a host span at each of its layer boundaries
(``repro.obs``): ``repro.round``, ``repro.local_round``,
``repro.cohort_step`` (arg ``family``), ``repro.deliver``,
``repro.fire``, and ``repro.host_sync`` (arg ``what``) around every
device-to-host read. Host spans and device events share the profiler's
clock, so the reduction pairs them with chip 0's device activity:

  * the ``repro.*`` spans that start inside the window, with their args;
  * chip 0's op intervals, merged, and so the device's idle gaps;
  * chip 0's ``XLA Modules`` events (one per executed program) in time
    order, named by their jitted function.

``latest()`` reads the newest ``.xplane.pb`` under
``<root>/.bench_cache/trace/`` (where the harness writes a traced run's
trace), parsing each file once per process. It is ``None`` where the
trace holds no ``repro.*`` span, as for a program without them; so is
every reader built on it.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import statistics
from typing import Dict, List, Optional, Tuple

from bench import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = os.path.join(ROOT, ".bench_cache", "trace")
PREFIX = "repro."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float                     # ns on the profiler's clock
    end: float
    args: dict


@dataclasses.dataclass
class Spans:
    lo: float                        # the window, ns
    hi: float
    spans: List[Span]                # repro.* spans starting in the window
    busy: List[List[float]]          # chip 0's merged op intervals
    modules: List[Tuple[float, float, str]]   # chip 0's programs

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def host_s(self, name: str) -> float:
        """Host seconds inside the spans called ``name``."""
        return sum(s.end - s.start for s in self.named(name)) * 1e-9

    def idle_gaps(self) -> List[Tuple[float, float]]:
        edges = [self.lo] + [x for iv in self.busy for x in iv] + [self.hi]
        return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]

    def sync_idle_s(self) -> float:
        """Device-idle seconds in gaps that start while the host waits in
        a ``repro.host_sync`` read: the device drained its queue because
        the host was waiting on it."""
        syncs = sorted((s.start, s.end) for s in self.named("repro.host_sync"))
        starts = [s for s, _ in syncs]
        idle = 0.0
        for s, e in self.idle_gaps():
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < syncs[i][1]:
                idle += e - s
        return idle * 1e-9

    def cohort_step_s(self) -> Optional[Dict[str, float]]:
        """Device seconds of ``jit__cohort_step`` by family: the i-th
        ``repro.cohort_step`` span launches the i-th such program on chip
        0, which runs programs in launch order. ``None`` where the two
        counts differ."""
        steps = self.named("repro.cohort_step")
        runs = [(s, e) for s, e, n in self.modules if n == "jit__cohort_step"]
        if not steps or len(steps) != len(runs):
            return None
        out: Dict[str, float] = {}
        for span, (s, e) in zip(steps, runs):
            fam = span.args["family"]
            out[fam] = out.get(fam, 0.0) + (min(e, self.hi)
                                            - max(s, self.lo)) * 1e-9
        return out

    def fire_s(self) -> List[float]:
        """Per-fire seconds: from one ``repro.deliver`` span's start to the
        next; the last fire ends at the window's end."""
        starts = [s.start for s in self.named("repro.deliver")]
        return [(b - a) * 1e-9 for a, b in zip(starts, starts[1:] + [self.hi])]


def reduce(path: str) -> Optional[Spans]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: List[Span] = []
    window = None
    chips = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                chips.append((int(plane.name.rsplit(":", 1)[1]), lines))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(PREFIX):
                        host.append(Span(ev.name, ev.start_ns, ev.end_ns,
                                         dict(ev.stats)))
                    elif ev.name == tr.WINDOW_SPAN and window is None:
                        window = (ev.start_ns, ev.end_ns)
    if not host or not chips:
        return None
    lines = min(chips, key=lambda c: c[0])[1]
    ops = [(ev.start_ns, ev.end_ns) for ev in lines["XLA Ops"].events]
    if not ops:
        return None
    lo, hi = window or (min(s for s, _ in ops), max(e for _, e in ops))
    programs = lines["XLA Modules"].events if "XLA Modules" in lines else ()
    mods = sorted((ev.start_ns, ev.end_ns, tr.module_name(ev.name))
                  for ev in programs if ev.end_ns > lo and ev.start_ns < hi)
    spans = sorted((s for s in host if lo <= s.start < hi),
                   key=lambda s: s.start)
    return Spans(lo=lo, hi=hi, spans=spans,
                 busy=tr._merge(tr._clip(ops, lo, hi)), modules=mods)


@functools.lru_cache(maxsize=2)
def _reduce_once(path: str, mtime: float) -> Optional[Spans]:
    return reduce(path)


def latest() -> Optional[Spans]:
    """The newest trace under ``TRACES``, reduced; ``None`` where there is
    none or it holds no ``repro.*`` span."""
    files = glob.glob(os.path.join(TRACES, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    return _reduce_once(path, os.path.getmtime(path))


def percentile(values: List[float], p: int) -> float:
    """The ``p``-th percentile, interpolated between the values."""
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=100, method="inclusive")[p - 1]
