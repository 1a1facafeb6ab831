"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<i>``) with an
``XLA Modules`` line (one event per executed program, named
``jit_<fn>(<id>)``) and an ``XLA Ops`` line (one event per HLO op, named
by its HLO text), and host planes whose ``python`` line carries the
benchmark's own ``TraceAnnotation`` spans. Device and host events share
one clock, in nanoseconds from the start of the trace.

The reduction gives, inside the window the benchmark marks with its
``bench.window`` span:

  * device busy time: the union of the op intervals, averaged over chips;
  * the idle share;
  * device time and call count per XLA module (programs are named by
    their enclosing jitted function, which is how a Pallas kernel, whose
    body is always named ``_kernel``, is found);
  * the device ops that took most time, as ``module/op``;
  * the idle gaps, each attributed to the innermost benchmark span the
    host had open at the gap's midpoint.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "host outside the benchmark's spans"
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                              # averaged over chips
    chips: int
    module_s: Dict[str, float]                 # summed over chips
    module_calls: Dict[str, int]               # on chip 0
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]         # chip 0, by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, name: str) -> Tuple[float, int]:
        """Device seconds per chip and calls on chip 0 of the modules
        named ``name`` once the program id is cut off."""
        return (self.module_s.get(name, 0.0) / self.chips,
                self.module_calls.get(name, 0))


def find_trace(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def module_name(event_name: str) -> str:
    return _MODULE_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def _merge(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def reduce_trace(path: str, top: int = 10) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append((plane.name, lines))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane with XLA ops")
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    per_chip_ops = []
    for _, lines in devices:
        per_chip_ops.append([(ev.start_ns, ev.end_ns, ev.name)
                             for ev in lines["XLA Ops"].events])
    if win:
        lo, hi = win[0]
    else:
        allv = [x for ops in per_chip_ops for x in ops]
        lo, hi = min(x[0] for x in allv), max(x[1] for x in allv)
    busy = []
    module_s: Dict[str, float] = collections.defaultdict(float)
    module_calls: Dict[str, int] = collections.Counter()
    op_s: Dict[str, float] = collections.defaultdict(float)
    merged0: List[List[float]] = []
    for chip, ((_, lines), ops) in enumerate(zip(devices, per_chip_ops)):
        mods = sorted((ev.start_ns, ev.end_ns, module_name(ev.name))
                      for ev in lines.get("XLA Modules", ()).events
                      if ev.end_ns > lo and ev.start_ns < hi)
        for s, e, name in mods:
            module_s[name] += (min(e, hi) - max(s, lo)) * 1e-9
            if chip == 0:
                module_calls[name] += 1
        starts = [m[0] for m in mods]
        merged = _merge(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if chip == 0:
            merged0 = merged
            for s, e, name in ops:
                if e <= lo or s >= hi:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                op_s[f"{mod}/{op_name(name)}"] += (min(e, hi)
                                                   - max(s, lo)) * 1e-9
    gaps: Dict[str, float] = collections.defaultdict(float)
    edges = [lo] + [x for iv in merged0 for x in iv] + [hi]
    inner = sorted((s, e, n) for s, e, n in spans if n != WINDOW_SPAN)
    active: List[Tuple[float, float, str]] = []
    j = 0
    for s, e in zip(edges[0::2], edges[1::2]):       # gaps in time order
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        while j < len(inner) and inner[j][0] <= mid:
            active.append(inner[j])
            j += 1
        active = [a for a in active if a[1] > mid]
        name = (min(active, key=lambda a: a[1] - a[0])[2] if active
                else NO_SPAN)
        gaps[name] += (e - s) * 1e-9
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / len(busy),
        chips=len(devices), module_s=dict(module_s),
        module_calls=dict(module_calls),
        top_ops=sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1])[:top])


def breakdown(red: Reduced) -> dict:
    return {"device_ops": [[n, s] for n, s in red.top_ops],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
