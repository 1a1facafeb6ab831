"""The program's profiler spans (``repro.obs``): recorded on the CPU for
one small sync round of two families and one delta fire, read back from
the trace with ``ProfileData``."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Federation, FederationConfig, FederationEngine,
                        Protocol, init_server)
from repro.core.policies import as_policy
from repro.core.runtime import ServerBus
from repro.data import make_splits, pad_like
from repro.models.zoo import build_zoo
from repro.obs import host_read

TABLE = {"repro.round", "repro.local_round", "repro.cohort_step",
         "repro.cohort_batch", "repro.collect_messengers", "repro.upload",
         "repro.assemble", "repro.deliver", "repro.fire", "repro.grade",
         "repro.build_graph", "repro.emit_targets", "repro.div_update",
         "repro.select", "repro.downlink", "repro.host_sync"}
FIRE_READS = {"deliver.mask", "select.pool", "fire.receivers"}


def _spans(log_dir):
    """(name, start, end, args) of every repro.* host span, by start."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                        for ev in ln.events
                        if ev.name.startswith("repro.")]
    return sorted(out, key=lambda s: s[1])


def _traced(log_dir, fn):
    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _spans(log_dir)


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _each_inside(spans, inner, outer):
    outs = _named(spans, outer)
    ins = _named(spans, inner)
    assert ins and outs
    for _, s, e, _ in ins:
        assert any(a <= s and e <= b for _, a, b, _ in outs), (inner, outer)


def _engine(ds, splits):
    zoo = build_zoo("mlp-s,resnet", ds.feature_len, ds.n_classes)
    return FederationEngine.build(
        ds, splits, zoo, None, Protocol("sqmd", q=8, k=4),
        config=FederationConfig(rounds=2, batch_size=8), seed=3)


@pytest.fixture(scope="module")
def sync(tmp_path_factory):
    """Rounds 0 and 1 of two same-seed engines, round 1 of the first
    traced; returns (spans, traced engine, untraced engine)."""
    ds = pad_like(samples_per_client=16, ref_size=12, length=16)
    splits = make_splits(ds, seed=0)
    traced, plain = _engine(ds, splits), _engine(ds, splits)
    traced.run_round(0)
    spans = _traced(tmp_path_factory.mktemp("sync"),
                    lambda: traced.run_round(1))
    plain.run_round(0)
    plain.run_round(1)
    return spans, traced, plain


def _bus(n=8, r=6, c=3):
    fed = Federation(cohorts=[], server=init_server(n, r, c),
                     protocol=Protocol("sqmd", q=n, k=2),
                     ref_x=jnp.zeros((r, 4)),
                     ref_y=jnp.asarray(np.arange(r) % c), optimizer=None,
                     n_clients=n)
    return ServerBus(fed, as_policy(fed.protocol), trigger="every-upload",
                     delta=True)


def _msg(seed, n=8, r=6, c=3):
    return jax.nn.log_softmax(
        jax.random.normal(jax.random.key(seed), (n, r, c)) * 2, -1)


@pytest.fixture(scope="module")
def fire(tmp_path_factory):
    """A delta fire of 3 rows after a full upload, traced."""
    bus = _bus()
    bus.deliver(0.0, _msg(0), np.ones(8, bool))
    part = np.zeros(8, bool)
    part[[1, 4, 6]] = True
    spans = _traced(tmp_path_factory.mktemp("fire"),
                    lambda: bus.deliver(1.0, _msg(1), part))
    return spans, bus


def test_every_span_of_the_table_appears(sync, fire):
    names = {s[0] for s in sync[0]} | {s[0] for s in fire[0]}
    assert names == TABLE
    # the full rebuild of a sync round has no delta update
    assert not _named(sync[0], "repro.div_update")


@pytest.mark.parametrize("inner,outer", [
    ("repro.local_round", "repro.round"),
    ("repro.cohort_step", "repro.local_round"),
    ("repro.cohort_batch", "repro.cohort_step"),
    ("repro.collect_messengers", "repro.round"),
    ("repro.upload", "repro.collect_messengers"),
    ("repro.assemble", "repro.collect_messengers"),
    ("repro.deliver", "repro.round"),
    ("repro.fire", "repro.deliver"),
    ("repro.grade", "repro.fire"),
    ("repro.build_graph", "repro.fire"),
    ("repro.emit_targets", "repro.fire"),
    ("repro.select", "repro.build_graph"),
    ("repro.downlink", "repro.fire"),
])
def test_sync_round_spans_nest(sync, inner, outer):
    _each_inside(sync[0], inner, outer)


@pytest.mark.parametrize("inner,outer", [
    ("repro.fire", "repro.deliver"),
    ("repro.grade", "repro.fire"),
    ("repro.build_graph", "repro.fire"),
    ("repro.emit_targets", "repro.fire"),
    ("repro.div_update", "repro.build_graph"),
    ("repro.select", "repro.build_graph"),
    ("repro.downlink", "repro.fire"),
])
def test_delta_fire_spans_nest(fire, inner, outer):
    _each_inside(fire[0], inner, outer)


def test_cohort_step_carries_each_family(sync):
    spans, eng, _ = sync
    steps = _named(spans, "repro.cohort_step")
    sizes = {c.family_name: c.n_clients for c in eng.fed.cohorts}
    assert {a["family"]: a["clients"] for *_, a in steps} == sizes
    assert sorted(sizes) == ["mlp-s", "resnet"]
    batches = _named(spans, "repro.cohort_batch")
    assert [a["family"] for *_, a in batches] == \
        [a["family"] for *_, a in steps]
    assert {a["family"] for *_, a in _named(spans, "repro.upload")} == \
        set(sizes)
    assert _named(spans, "repro.round")[0][3]["round"] == 1


def test_fire_host_reads_are_the_documented_set(sync, fire):
    for spans in (sync[0], fire[0]):
        whats = [a["what"] for *_, a in _named(spans, "repro.host_sync")]
        assert sorted(whats) == sorted(FIRE_READS)
    _each_inside(fire[0], "repro.host_sync", "repro.deliver")


def test_fire_args(sync, fire):
    spans, bus = fire
    (_, _, _, deliver), = _named(spans, "repro.deliver")
    (_, _, _, fired), = _named(spans, "repro.fire")
    # the fire shares its deliver's identifier: the fire it triggered
    assert deliver["fire"] == fired["fire"] == bus.n_triggers - 1
    assert deliver["rows"] == fired["rows"] == 3 and fired["delta"] == 1
    (_, _, _, upd), = _named(spans, "repro.div_update")
    assert upd == {"rows": 3, "bucket": 4}
    (_, _, _, sel), = _named(spans, "repro.select")
    assert sel == {"pool": 8, "bucket": 8}
    paths = {a["path"] for *_, a in _named(spans, "repro.build_graph")}
    assert paths == {"delta"}
    paths = {a["path"] for *_, a in _named(sync[0], "repro.build_graph")}
    assert paths == {"full"}


def test_bus_keeps_no_per_fire_staleness(fire):
    _, bus = fire
    assert not hasattr(bus, "last_staleness")
    assert bus.staleness(1.0)["n"] == 8


def test_outputs_are_bit_identical_with_the_profiler_on(sync):
    _, traced, plain = sync
    for a, b in zip(traced.fed.cohorts, plain.fed.cohorts):
        for x, y in zip(jax.tree.leaves((a.params, a.opt_state)),
                        jax.tree.leaves((b.params, b.opt_state))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves((traced.fed.server,
                                     traced.fed.targets)),
                    jax.tree.leaves((plain.fed.server, plain.fed.targets))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_host_read_allows_the_read_under_a_disallowing_guard():
    x = jnp.arange(4) > 1
    with jax.transfer_guard_device_to_host("disallow"):
        got = host_read(x, "test", bool)
    assert got.dtype == bool and got.tolist() == [False, False, True, True]
