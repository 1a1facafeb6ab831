"""One run of one cell: set-up, the measured window, the traced window's
per-layer metrics and the output check, printed as one JSON line.

Everything that belongs to one cell is found by name:

  * ``BENCHMARK.json`` names the cell's configuration, traffic and chips;
  * the configuration file is the one ``BENCHMARK.json`` gives;
  * ``bench/traffic/<traffic>.json`` holds the traffic's parameters and
    the name of the driver that generates it, ``bench/drivers/<driver>.py``;
  * ``bench/limits/<cell>.json`` holds the limit of each number the
    output check compares;
  * each per-layer metric is read by ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(".bench_cache", "jax")
TRACES = os.path.join(".bench_cache", "trace")
UNSOUND = 1e30


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    # every metric names its cells, setup_s (every cell's) aside
    e2e = [m for m in spec["end_to_end"]
           if m["name"] == "setup_s" or name in m["workloads"]]
    per_layer = [m for m in spec["per_layer"] if name in m["workloads"]]
    bench = os.path.join(root, "bench")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read_json(os.path.join(root, conf["file"])),
        traffic=_read_json(os.path.join(bench, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(bench, "limits", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def load_driver(cell: Cell):
    mod = importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")
    return mod.Driver


def load_reader(metric: str, root: str = ROOT) -> Callable:
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_of(kind: str, root: str = ROOT) -> dict:
    table = _read_json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no accelerator: platform "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chip(s) found, the cell asks for {chips}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    keeping every program, not only those slow to compile."""
    import jax
    path = os.path.join(root, CACHE)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_peak(n: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Programs compiled or loaded from the persistent cache while on."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kwargs):
        if self.on and event in self._EVENTS:
            self.count += 1


def _span(label: str, fn):
    import jax

    @functools.wraps(fn)
    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(label):
            return fn(*a, **k)
    return wrapped


@contextlib.contextmanager
def spans_on(targets):
    """Wrap the given instance methods in benchmark spans, on the
    instances only, and restore them afterwards."""
    saved = []
    for obj, attr, label in targets:
        saved.append((obj, attr, obj.__dict__.get(attr)))
        setattr(obj, attr, _span("bench." + label, getattr(obj, attr)))
    try:
        yield
    finally:
        for obj, attr, old in saved:
            if old is None:
                delattr(obj, attr)
            else:
                setattr(obj, attr, old)


def _traced_window(drv, seconds: float, trace_dir: str):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # the benchmark's spans only:
    opts.host_tracer_level = 1         # no runtime-internal host events
    opts.enable_hlo_proto = False
    with spans_on(drv.spans()):
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                out = drv.window(seconds)
        finally:
            jax.profiler.stop_trace()
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description="One run of one benchmark "
                                             "cell; prints one JSON line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, t_start: float, root: str = ROOT, require_chip: bool = True,
        peak_kind: Optional[str] = None, driver=None) -> dict:
    """The run as a result dict; raises ``NoChip`` before any work where
    the chip is missing. Tests pass ``require_chip=False`` and a
    ``driver`` (an instance, possibly with a fault planted)."""
    cell = load_cell(args.workload, root)
    import jax
    if require_chip:
        device = device_info(cell.chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": cell.chips}
    peak = peak_of(peak_kind or device["kind"], root)
    enable_cache(root)
    drv = driver or load_driver(cell)(cell.config, cell.traffic, args.seed)
    drv.setup()
    counter = CompileCounter()
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    counter.on = True
    if args.trace:
        trace_dir = os.path.join(root, TRACES, cell.name)
        out = _traced_window(drv, args.seconds, trace_dir)
    else:
        out = drv.window(args.seconds)
    counter.on = False
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    metrics: Dict[str, dict] = {}
    result = {"correct": False, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": device}
    if args.trace:
        from bench import trace as tr
        red = tr.reduce_trace(tr.find_trace(trace_dir))
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        for m in cell.per_layer:
            value = load_reader(m["name"], root)(red, out["counters"], peak)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = tr.breakdown(red)
    else:
        values = dict(out["e2e"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result["compiles_in_window"] = counter.count
    drv.release()
    t_check = time.perf_counter()
    checks = {}
    for name, value in drv.check():
        # an unsound answer (NaN or infinite) reads as a number no
        # limit admits
        value = float(value) if math.isfinite(value) else UNSOUND
        checks[name] = {"value": value, "limit": cell.limits[name]}
    result["check_s"] = time.perf_counter() - t_check
    result["correct"] = (out["failed"] == 0 and bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result["checks"] = checks
    return result


def main(argv, t_start: float) -> int:
    args = parse(argv)
    try:
        result = run(args, t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"check correct = {result['correct']}", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
