"""The readers of the program's ``repro.*`` spans (``bench/spans.py``), on
traces recorded on a TPU v5e chip with the harness's trace options, each
inside a ``bench.window`` span: three sync rounds of ``sc-sync`` and
three delta fires of ``srv16k-delta``. The older trace
(``srv_fire.xplane.pb.gz``) holds no ``repro.*`` span: it was recorded
from the program before it had them."""
import gzip
import os
import shutil

import pytest

from bench import harness, spans, trace
from bench.spans import Span, Spans

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
ROUND_READERS = ["client_dispatch_ms", "cohort_step_ms.mlp-s",
                 "cohort_step_ms.resnet", "cohort_step_ms.transformer",
                 "cohort_step_ms.ssm", "sync_idle_ms.round"]
FIRE_READERS = ["sync_idle_ms.fire", "fire_p90_ms"]
STEPS = 3


def _unpack(tmp_path_factory, name):
    out = tmp_path_factory.mktemp(name) / "run" / "t.xplane.pb"
    out.parent.mkdir()
    with gzip.open(os.path.join(FIXTURES, name + ".xplane.pb.gz")) as src, \
            open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(out)


@pytest.fixture(scope="module")
def sc_path(tmp_path_factory):
    return _unpack(tmp_path_factory, "sc_rounds")


@pytest.fixture(scope="module")
def srv_path(tmp_path_factory):
    return _unpack(tmp_path_factory, "srv_delta_fires")


@pytest.fixture(scope="module")
def old_path(tmp_path_factory):
    return _unpack(tmp_path_factory, "srv_fire")


def _read(monkeypatch, path, metric, counters):
    """The metric's reader, finding ``path`` as the newest trace."""
    monkeypatch.setattr(spans, "TRACES", os.path.dirname(
        os.path.dirname(path)))
    return harness.load_reader(metric)(trace.reduce_trace(path), counters,
                                       {})


def test_family_cohort_steps_sum_to_the_whole(monkeypatch, sc_path):
    counters = {"rounds": STEPS}
    fams = [_read(monkeypatch, sc_path, m, counters)
            for m in ROUND_READERS[1:5]]
    whole = _read(monkeypatch, sc_path, "cohort_step_ms", counters)
    assert all(f is not None and f > 0 for f in fams)
    assert sum(fams) == pytest.approx(whole, rel=0.01)
    # the ResNet-1D clients hold most of a round's FLOPs
    assert max(fams) == fams[1]


def test_dispatch_is_host_time_inside_the_local_rounds(monkeypatch,
                                                       sc_path):
    sp = spans.reduce(sc_path)
    rounds = sp.named("repro.round")
    assert len(rounds) == STEPS
    got = _read(monkeypatch, sc_path, "client_dispatch_ms",
                {"rounds": STEPS})
    round_ms = sum(s.end - s.start for s in rounds) * 1e-6 / STEPS
    assert 0 < got < round_ms


@pytest.mark.parametrize("path,metric,key", [
    ("sc", "sync_idle_ms.round", "rounds"),
    ("srv", "sync_idle_ms.fire", "fires")])
def test_sync_idle_is_part_of_the_idle(monkeypatch, sc_path, srv_path, path,
                                       metric, key):
    path = sc_path if path == "sc" else srv_path
    red = trace.reduce_trace(path)
    got = _read(monkeypatch, path, metric, {key: STEPS})
    idle_ms = 1e3 * (red.window_s - red.busy_s) / STEPS
    assert got is not None and 0 <= got <= idle_ms


def test_fire_tail_is_at_least_the_median(monkeypatch, srv_path):
    got = _read(monkeypatch, srv_path, "fire_p90_ms", {"fires": STEPS})
    times = sorted(spans.reduce(srv_path).fire_s())
    assert len(times) == STEPS
    assert times[-1] * 1e3 >= got >= times[STEPS // 2] * 1e3


def test_fire_spans_carry_the_documented_reads(srv_path):
    sp = spans.reduce(srv_path)
    whats = {s.args["what"] for s in sp.named("repro.host_sync")}
    assert whats == {"deliver.mask", "select.pool", "fire.receivers"}
    assert len(sp.named("repro.deliver")) == STEPS


@pytest.mark.parametrize("metric", ROUND_READERS + FIRE_READERS)
def test_every_reader_is_silent_without_program_spans(monkeypatch, old_path,
                                                      metric):
    assert spans.reduce(old_path) is None
    assert _read(monkeypatch, old_path, metric,
                 {"rounds": STEPS, "fires": STEPS}) is None


def test_readers_are_silent_without_a_trace(monkeypatch, tmp_path):
    monkeypatch.setattr(spans, "TRACES", str(tmp_path))
    assert spans.latest() is None


def _toy(steps, runs):
    mods = [(s, e, "jit__cohort_step") for s, e in runs]
    return Spans(lo=0, hi=100, busy=[[0, 15], [40, 60], [70, 80]],
                 modules=mods + [(61, 62, "jit_other")], spans=steps + [
                     Span("repro.host_sync", 10, 30, {"what": "a"}),
                     Span("repro.host_sync", 62, 65, {"what": "b"}),
                     Span("repro.deliver", 5, 9, {}),
                     Span("repro.deliver", 45, 50, {})])


def test_span_reduction_on_a_toy_trace():
    steps = [Span("repro.cohort_step", 1, 2, {"family": "a"}),
             Span("repro.cohort_step", 3, 4, {"family": "b"}),
             Span("repro.cohort_step", 5, 6, {"family": "a"})]
    sp = _toy(steps, [(0, 10), (40, 50), (70, 80)])
    # gaps 15-40 (starts inside the first read) and 60-70 (the second
    # read starts after the device went idle) and 80-100
    assert sp.idle_gaps() == [(15, 40), (60, 70), (80, 100)]
    assert sp.sync_idle_s() == pytest.approx(25e-9)
    assert sp.cohort_step_s() == pytest.approx({"a": 20e-9, "b": 10e-9})
    assert sp.fire_s() == pytest.approx([40e-9, 55e-9])
    assert sp.host_s("repro.host_sync") == pytest.approx(23e-9)
    # a span whose program is missing pairs nothing
    assert _toy(steps, [(0, 10), (40, 50)]).cohort_step_s() is None
    assert spans.percentile([1.0, 2.0, 3.0], 50) == 2.0
    assert spans.percentile([4.0], 90) == 4.0
