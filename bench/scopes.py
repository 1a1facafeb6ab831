"""Device time by the program's ``jax.named_scope``: the chip-0 ``XLA Ops``
events, inside the ``bench.window`` span, whose op carries a scope in its
HLO ``op_name`` (the trace's ``tf_op`` stat, e.g.
``jit(_cohort_step)/.../nemotron_h.moe/dot_general``).

``jax.profiler.ProfileData`` gives events' own stats but not their
metadata's, where ``tf_op`` lives, so the ``.xplane.pb`` is read as the
XSpace protobuf with the ``xplane_pb2`` module that the installed
TensorFlow ships (loaded from its file; TensorFlow itself is not
imported). Where that module, the trace or the scope is missing, the
readers built on ``device_s`` return ``None``.
"""
from __future__ import annotations

import functools
import glob
import importlib.util
import os
from typing import List, Optional, Tuple

from bench import spans
from bench import trace as tr

OPS_LINE = "XLA Ops"


@functools.lru_cache(maxsize=1)
def _xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    if not os.path.exists(path):
        return None
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def ops(path: str) -> List[Tuple[float, float, str]]:
    """Chip 0's XLA ops as (start ns, end ns, tf_op), on the profiler's
    clock."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return []
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    chips = [p for p in space.planes if p.name.startswith("/device:TPU:")
             and any(ln.name == OPS_LINE for ln in p.lines)]
    if not chips:
        return []
    plane = min(chips, key=lambda p: int(p.name.rsplit(":", 1)[1]))
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    tf_op = {}
    for k, md in plane.event_metadata.items():
        for st in md.stats:
            if stat_names.get(st.metadata_id) == "tf_op":
                tf_op[k] = (st.str_value if st.HasField("str_value")
                            else stat_names.get(st.ref_value, ""))
    out = []
    for line in plane.lines:
        if line.name != OPS_LINE:
            continue
        for ev in line.events:
            start = line.timestamp_ns + ev.offset_ps * 1e-3
            out.append((start, start + ev.duration_ps * 1e-3,
                        tf_op.get(ev.metadata_id, "")))
    return out


@functools.lru_cache(maxsize=2)
def _ops_once(path: str, mtime: float):
    return ops(path)


def device_s(scope: str) -> Optional[float]:
    """Chip-0 seconds in the newest trace's window in which an op runs
    whose ``tf_op`` carries ``scope`` as a path component; ``None`` where
    no op does. The ops' intervals are merged, since a loop's op spans
    the ops of its body."""
    sp = spans.latest()
    files = glob.glob(os.path.join(spans.TRACES, "**", "*.xplane.pb"),
                      recursive=True)
    if sp is None or not files:
        return None
    path = max(files, key=os.path.getmtime)
    hits = [(s, e) for s, e, name in _ops_once(path, os.path.getmtime(path))
            if scope in name.split("/")]
    if not hits:
        return None
    merged = tr._merge(tr._clip(hits, sp.lo, sp.hi))
    return sum(e - s for s, e in merged) * 1e-9
