"""The trace reduction, on a trace recorded on a TPU v5e chip: three delta
fires of the N=16384 server, each inside a ``bench.deliver`` span."""
import gzip
import os
import shutil

import pytest

from bench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "srv_fire.xplane.pb.gz")


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "srv.xplane.pb"
    with gzip.open(FIXTURE) as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(out)


@pytest.fixture(scope="module")
def red(path):
    return trace.reduce_trace(path)


def _raw_ops(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    plane = pd.find_plane_with_name("/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    return [(ev.start_ns, ev.end_ns) for ev in line.events]


def test_busy_is_the_union_of_op_intervals(path, red):
    ops = _raw_ops(path)
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    # no window span in this trace: the window is the ops' extent
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    # brute force union on a 1-microsecond grid
    covered = set()
    for s, e in ops:
        covered.update(range(int(s // 1000), int(-(-e // 1000))))
    assert red.busy_s == pytest.approx(len(covered) * 1e-6, rel=0.02)
    assert 0.0 < red.busy_s < red.window_s
    assert red.idle_share == pytest.approx(1 - red.busy_s / red.window_s)
    assert red.chips == 1


def test_modules_are_found_by_their_jitted_function(red):
    # Pallas kernel bodies are all named _kernel; the enclosing jitted
    # function names the program
    assert red.module_calls["jit_neighbor_mean"] == 3
    assert red.module_calls["jit__pair_call"] == 6      # row + column strip
    assert red.module_calls["jit__select_pool_div"] == 3
    nm, calls = red.module_time("jit_neighbor_mean")
    assert calls == 3 and 0.0 < nm < red.busy_s
    assert sum(red.module_s.values()) <= red.window_s


def test_top_ops_name_their_module(red):
    names = [n for n, _ in red.top_ops]
    assert names[0] == "jit_neighbor_mean/neighbor_mean.1"
    assert all("/" in n for n in names)
    secs = [s for _, s in red.top_ops]
    assert secs == sorted(secs, reverse=True)


def test_idle_gaps_are_attributed_to_the_open_host_span(red):
    gaps = dict(red.idle_gaps)
    # every gap between fires falls inside a deliver span
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)
    assert max(gaps, key=gaps.get) == "bench.deliver"
    bd = trace.breakdown(red)
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
