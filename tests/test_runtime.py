"""Tests for the event-driven virtual-clock runtime: sync-parity pin,
Clock/Event ordering, server triggers, arrival processes, the three async
regimes the redesign exists for (straggler latency, bursty arrivals,
quorum-triggered server rounds), and History/precision_recall metrics."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AlwaysOn, AsyncFederationEngine, BurstyArrivals,
                        Clock, EveryKUploads, EveryUpload, Federation,
                        FederationConfig, FederationEngine,
                        HeterogeneousCadence, History, Quorum,
                        ScheduleArrivals, ServerBus, StagedJoin,
                        StragglerLatency, SyncClock, WallInterval,
                        as_arrivals, as_trigger, get_arrivals, get_trigger,
                        init_server, isgd, precision_recall,
                        registered_arrivals, registered_triggers,
                        selection_matrix, sqmd, staleness_summary)
from repro.core.client import Cohort
from repro.data import make_splits, pad_like
from repro.models.mlp import hetero_mlp_zoo
from repro.optim import sgd


@pytest.fixture(scope="module")
def setup():
    """EXACTLY the pre-runtime pin fixture — test_sync_parity_pinned's
    PINNED_* values were captured at this scale; do not shrink."""
    ds = pad_like(samples_per_client=30, ref_size=30, length=24)
    splits = make_splits(ds, seed=0)
    zoo = hetero_mlp_zoo(ds.feature_len, ds.n_classes)
    assignment = [list(zoo)[i % 3] for i in range(ds.n_clients)]
    return ds, splits, zoo, assignment


@pytest.fixture(scope="module")
def setup_small():
    """Small fixture for the async-regime and shim-parity tests (they
    compare engines against each other on the SAME data, so the scale is
    free to shrink for CI speed)."""
    ds = pad_like(samples_per_client=16, ref_size=16, length=16)
    splits = make_splits(ds, seed=0)
    zoo = hetero_mlp_zoo(ds.feature_len, ds.n_classes)
    assignment = [list(zoo)[i % 3] for i in range(ds.n_clients)]
    return ds, splits, zoo, assignment


CFG = dict(rounds=4, batch_size=8, eval_every=2)


# --- sync parity (acceptance: bit-identical to the pre-redesign loop) -----

# Captured from the pre-runtime round-synchronous FederationEngine at
# commit 8d68e9c with exactly this setup (pad_like(30, 30, 24), splits
# seed 0, sqmd(q=8, k=4), rounds=4, batch 8, eval_every=2, seed=7).
PINNED_MEAN_ACC = [0.7023809626698494, 0.7500000095793179,
                   0.7976190575531551]
PINNED_VAL_ACC = [0.7619047707745007, 0.8095238187483379,
                  0.8452381044626236]


def test_sync_parity_pinned(setup):
    """FederationEngine on the event runtime reproduces the pre-redesign
    same-seed History trajectory exactly."""
    ds, splits, zoo, assignment = setup
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(**CFG), seed=7)
    h = engine.fit(splits)
    np.testing.assert_allclose(h.mean_acc, PINNED_MEAN_ACC, rtol=0,
                               atol=1e-9)
    np.testing.assert_allclose(h.val_acc, PINNED_VAL_ACC, rtol=0, atol=1e-9)
    # the sync engine is the SyncClock + every-upload special case
    assert isinstance(engine.clock, SyncClock)
    assert isinstance(engine.bus.trigger, EveryUpload)
    assert h.rounds == [0, 2, 3]
    assert h.times == [0.0, 2.0, 3.0]
    assert h.server_rounds == [1, 3, 4]    # one policy fire per round
    # always-on + interval=1: every repository row is fresh at eval
    assert h.staleness[-1]["n"] == ds.n_clients
    assert h.staleness[-1]["n_stale"] == 0


def test_async_shim_matches_sync(setup_small):
    """ScheduleArrivals + every-upload on the event loop is the sync
    engine: identical trajectories for always-on AND staged-join."""
    ds, splits, zoo, assignment = setup_small
    join = [0] * (ds.n_clients - 6) + [2] * 6
    for schedule in (AlwaysOn(), StagedJoin(join)):
        sync = FederationEngine.build(
            ds, splits, zoo, assignment, sqmd(q=8, k=4),
            config=FederationConfig(**CFG), schedule=schedule, seed=5)
        h_sync = sync.fit(splits)
        asyn = AsyncFederationEngine.build(
            ds, splits, zoo, assignment, sqmd(q=8, k=4),
            arrivals=ScheduleArrivals(schedule),
            config=FederationConfig(**CFG), seed=5)
        h_async = asyn.fit(splits, until=3.0)
        assert h_async.rounds == h_sync.rounds
        assert h_async.times == h_sync.times
        np.testing.assert_allclose(h_async.mean_acc, h_sync.mean_acc,
                                   rtol=0, atol=1e-9)
        np.testing.assert_allclose(np.asarray(selection_matrix(
                                       asyn.last_graph)),
                                   np.asarray(selection_matrix(
                                       sync.last_graph)),
                                   rtol=0, atol=1e-9)


def test_async_shim_matches_sync_with_empty_rounds(setup_small):
    """Rounds where NO client is available still burn RNG splits and fire
    the (empty) communication round in the sync engine; the shim must
    reproduce that exactly."""
    ds, splits, zoo, assignment = setup_small
    join = [2] * ds.n_clients                  # nobody joins until round 2
    sync = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(**CFG), schedule=StagedJoin(join), seed=5)
    h_sync = sync.fit(splits)
    asyn = AsyncFederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        arrivals=ScheduleArrivals(StagedJoin(join)),
        config=FederationConfig(**CFG), seed=5)
    h_async = asyn.fit(splits, until=3.0)
    np.testing.assert_allclose(h_async.mean_acc, h_sync.mean_acc, rtol=0,
                               atol=1e-9)
    assert h_async.server_rounds == h_sync.server_rounds


def test_async_rejects_round_synchronous_interval(setup_small):
    """Protocol.interval is round-synchronous; the event engine demands a
    Trigger instead of silently communicating on every wake."""
    ds, splits, zoo, assignment = setup_small
    with pytest.raises(ValueError, match="Trigger"):
        AsyncFederationEngine.build(
            ds, splits, zoo, assignment,
            sqmd(q=8, k=4, interval=2), config=FederationConfig(**CFG))


# --- Clock / Event --------------------------------------------------------

def test_clock_orders_by_time_priority_fifo():
    clk = Clock()
    clk.schedule(2.0, "wake", "w2")
    clk.schedule(1.0, "wake", "w1")
    clk.schedule(1.0, "upload", "u1")      # same time, higher priority
    clk.schedule(1.0, "wake", "w1b")       # FIFO within (time, kind)
    order = []
    while (ev := clk.pop_due(10.0)) is not None:
        order.append(ev.payload)
    assert order == ["u1", "w1", "w1b", "w2"]
    assert clk.now == 2.0


def test_clock_pop_due_respects_horizon():
    clk = Clock()
    clk.schedule(1.0, "wake")
    clk.schedule(5.0, "wake")
    assert clk.pop_due(2.0).time == 1.0
    assert clk.pop_due(2.0) is None        # 5.0 stays queued
    assert len(clk) == 1
    assert clk.pop_due(5.0).time == 5.0


def test_clock_rejects_past_events():
    clk = Clock()
    clk.schedule(3.0, "wake")
    clk.pop_due(5.0)
    with pytest.raises(ValueError, match="past"):
        clk.schedule(1.0, "wake")


# --- triggers -------------------------------------------------------------

def _bus_stub(n=10, uploads=0, fresh=0):
    return types.SimpleNamespace(
        uploads_since_fire=uploads,
        fresh_since_fire=np.arange(n) < fresh,
        fed=types.SimpleNamespace(n_clients=n))


def test_trigger_registry():
    assert set(registered_triggers()) >= {"every-upload", "every-k",
                                          "interval", "quorum"}
    assert get_trigger("quorum") is Quorum
    with pytest.raises(KeyError, match="unknown trigger"):
        get_trigger("no-such-trigger")
    assert isinstance(as_trigger(None), EveryUpload)
    assert isinstance(as_trigger("every-k"), EveryKUploads)
    t = as_trigger(WallInterval(period=2.0))
    assert t.wall_period() == 2.0


def test_trigger_predicates():
    assert EveryUpload().should_fire(0.0, _bus_stub())
    k = EveryKUploads(k=5)
    assert not k.should_fire(0.0, _bus_stub(uploads=4))
    assert k.should_fire(0.0, _bus_stub(uploads=5))
    q = Quorum(frac=0.5)
    assert not q.should_fire(0.0, _bus_stub(n=10, fresh=4))
    assert q.should_fire(0.0, _bus_stub(n=10, fresh=5))
    assert Quorum(count=2).should_fire(0.0, _bus_stub(n=10, fresh=2))
    w = WallInterval(period=1.5)
    assert w.should_fire_on_tick(0.0, _bus_stub())
    assert not w.should_fire(0.0, _bus_stub(uploads=100))
    with pytest.raises(ValueError, match="k must"):
        EveryKUploads(k=0)
    with pytest.raises(ValueError, match="frac"):
        Quorum(frac=0.0)
    with pytest.raises(ValueError, match="period"):
        WallInterval(period=0.0)


# --- arrival processes ----------------------------------------------------

def test_arrivals_registry_and_coercion():
    assert set(registered_arrivals()) >= {"schedule", "straggler-latency",
                                          "cadence", "bursty"}
    assert get_arrivals("bursty") is BurstyArrivals
    assert isinstance(as_arrivals(None), ScheduleArrivals)
    assert isinstance(as_arrivals("cadence"), HeterogeneousCadence)
    # a mask Schedule (instance or registered name) shims transparently
    assert isinstance(as_arrivals(StagedJoin([0, 1])), ScheduleArrivals)
    shim = as_arrivals("dropout")
    assert isinstance(shim, ScheduleArrivals)
    assert shim.schedule.name == "dropout"


def test_arrivals_are_deterministic_and_sorted():
    for proc in (ScheduleArrivals(AlwaysOn()),
                 StragglerLatency(fraction=0.4, delay=2.0, seed=3),
                 HeterogeneousCadence(fast=1.0, slow=2.5, seed=3),
                 BurstyArrivals(burst_every=2.0, frac=0.5, seed=3)):
        w1 = proc.wakes(12, 6.0)
        w2 = proc.wakes(12, 6.0)
        times = [t for t, _ in w1]
        assert times == sorted(times)
        assert all(0.0 <= t <= 6.0 + 1e-9 for t in times)
        for (t1, m1), (t2, m2) in zip(w1, w2):
            assert t1 == t2
            np.testing.assert_array_equal(m1, m2)
            assert m1.dtype == bool and m1.shape == (12,)


def test_straggler_latency_process():
    proc = StragglerLatency(fraction=0.5, delay=3.0, seed=1)
    slow = proc.slow_mask(10)
    assert slow.sum() == 5
    lat = proc.latency(0.0, np.ones(10, bool), 10)
    np.testing.assert_array_equal(lat, np.where(slow, 3.0, 0.0))
    # every client wakes every tick — nobody is masked out
    for _, mask in proc.wakes(10, 4.0):
        assert mask.all()


def test_heterogeneous_cadence_fast_devices_tick_more():
    proc = HeterogeneousCadence(fast=1.0, slow=4.0, seed=2)
    per = proc.periods(8)
    counts = np.zeros(8)
    for _, mask in proc.wakes(8, 12.0):
        counts += mask
    fastest, slowest = int(np.argmin(per)), int(np.argmax(per))
    assert counts[fastest] > counts[slowest]


def test_as_arrivals_validation():
    with pytest.raises(ValueError, match="fraction"):
        StragglerLatency(fraction=1.5)
    with pytest.raises(ValueError, match="burst_every"):
        BurstyArrivals(burst_every=0.0)
    with pytest.raises(ValueError, match="cadence"):
        ScheduleArrivals(cadence=0.0)
    with pytest.raises(ValueError, match="fast"):
        HeterogeneousCadence(fast=3.0, slow=1.0)


# --- ServerBus: stale rows are merged, never dropped ----------------------

def _tiny_fed(n=4, r=6, c=3):
    """A Federation stub around a real ServerState (no cohorts needed to
    exercise the bus)."""
    return Federation(cohorts=[], server=init_server(n, r, c),
                      protocol=sqmd(q=n, k=2),
                      ref_x=jnp.zeros((r, 4)),
                      ref_y=jnp.asarray(np.arange(r) % c),
                      optimizer=sgd(0.1), n_clients=n)


def _msg(seed, n=4, r=6, c=3):
    return jax.nn.log_softmax(
        jax.random.normal(jax.random.key(seed), (n, r, c)) * 2, -1)


def test_bus_merges_stale_rows_never_drops():
    """A delayed upload overwrites only its own row; everyone else's stale
    row survives every merge and policy fire in between."""
    from repro.core.policies import as_policy
    fed = _tiny_fed()
    bus = ServerBus(fed, as_policy(sqmd(q=4, k=2)), trigger="every-upload",
                    backend="jnp")
    m0, m1 = _msg(0), _msg(1)
    mask_all = np.ones(4, bool)
    only2 = np.zeros(4, bool)
    only2[2] = True

    assert bus.deliver(0.0, m0, mask_all)          # fires (every-upload)
    # t=5: only client 2 re-uploads, produced back at t=3 (latency 2)
    assert bus.deliver(5.0, m1, only2, produced_at=3.0)
    repo = np.asarray(fed.server.repo_logp)
    np.testing.assert_allclose(repo[2], np.asarray(m1)[2], atol=1e-6)
    for i in (0, 1, 3):                            # stale rows: merged m0
        np.testing.assert_allclose(repo[i], np.asarray(m0)[i], atol=1e-6)
    # staleness reflects content age: row 2 is 2 old at t=5, rest 5 old
    s = bus.staleness(5.0)
    assert s["n"] == 4 and s["n_stale"] == 4
    assert s["max"] == pytest.approx(5.0)
    assert s["mean"] == pytest.approx((5 + 5 + 2 + 5) / 4)
    assert bus.n_triggers == 2 and bus.n_uploads == 5


def test_bus_out_of_order_upload_is_superseded():
    """Newest content wins per row: a late arrival carrying OLDER content
    than the row already holds must not regress the repository."""
    from repro.core.policies import as_policy
    fed = _tiny_fed()
    bus = ServerBus(fed, as_policy(sqmd(q=4, k=2)), trigger="every-upload",
                    backend="jnp")
    only2 = np.zeros(4, bool)
    only2[2] = True
    fresh, stale = _msg(0), _msg(1)
    bus.deliver(5.0, fresh, only2, produced_at=4.0)
    # in-flight upload from an earlier wake arrives later (longer latency)
    bus.deliver(6.0, stale, only2, produced_at=2.0)
    np.testing.assert_allclose(np.asarray(fed.server.repo_logp)[2],
                               np.asarray(fresh)[2], atol=1e-6)
    assert bus.last_upload_t[2] == 4.0         # did not move backward


def test_bus_quorum_batches_distinct_uploaders():
    """Quorum fires on DISTINCT uploaders: the same client re-uploading
    does not advance the quorum."""
    from repro.core.policies import as_policy
    fed = _tiny_fed()
    bus = ServerBus(fed, as_policy(sqmd(q=4, k=2)),
                    trigger=Quorum(count=2), backend="jnp")
    one = np.zeros(4, bool)
    one[0] = True
    assert not bus.deliver(0.0, _msg(0), one)      # 1 distinct
    assert not bus.deliver(1.0, _msg(1), one)      # still 1 distinct
    other = np.zeros(4, bool)
    other[3] = True
    assert bus.deliver(2.0, _msg(2), other)        # quorum of 2 -> fire
    assert bus.n_triggers == 1
    assert not bus.fresh_since_fire.any()          # counters reset


def test_staleness_summary_edges():
    last = np.array([-np.inf, 0.0, 3.0, 9.5])
    active = np.array([True, True, True, True])
    s = staleness_summary(last, active, 10.0)
    assert s["n"] == 3                       # never-uploaded row excluded
    assert s["max"] == pytest.approx(10.0)
    assert s["hist"] == [1, 0, 0, 1, 1]      # ages 0.5, 7, 10
    empty = staleness_summary(np.full(3, -np.inf), np.ones(3, bool), 5.0)
    assert empty["n"] == 0 and empty["mean"] == 0.0


def test_bus_state_roundtrips_through_checkpoint(tmp_path):
    """Regression: restore_federation round-tripped params/codecs but NOT
    the bus's trigger counters, so a restored every-k engine double-fired
    or skipped its first server round. The bus state must resume exactly:
    the restored bus fires at the same delivery the uninterrupted one
    does."""
    from repro.checkpoint import restore_federation, save_federation
    from repro.core.policies import as_policy

    def mk():
        fed = _tiny_fed()
        bus = ServerBus(fed, as_policy(sqmd(q=4, k=2)),
                        trigger=EveryKUploads(k=3), backend="jnp")
        return fed, bus

    one = np.zeros(4, bool)
    one[0] = True
    other = np.zeros(4, bool)
    other[1] = True

    fed, bus = mk()
    assert not bus.deliver(0.0, _msg(0), one)       # 1/3 uploads
    assert not bus.deliver(1.0, _msg(1), other)     # 2/3
    save_federation(str(tmp_path), fed, step=1, bus=bus)

    fed2, bus2 = mk()
    restore_federation(str(tmp_path), fed2, bus=bus2)
    assert bus2.uploads_since_fire == 2
    assert bus2.fresh_since_fire.tolist() == bus.fresh_since_fire.tolist()
    np.testing.assert_array_equal(bus2.last_upload_t, bus.last_upload_t)
    assert bus2.n_uploads == 2 and bus2.n_triggers == 0
    np.testing.assert_array_equal(bus2.bytes_up, bus.bytes_up)

    # the third delivery fires BOTH buses — neither early nor late
    third = np.zeros(4, bool)
    third[2] = True
    assert bus.deliver(2.0, _msg(2), third)
    assert bus2.deliver(2.0, _msg(2), third)
    assert bus.n_triggers == bus2.n_triggers == 1
    # staleness bookkeeping resumed too (content ages, not -inf resets)
    assert bus.staleness(3.0) == bus2.staleness(3.0)


def test_bus_legacy_checkpoint_restores_zeroed_counters(tmp_path):
    """A checkpoint written WITHOUT a bus (the legacy format) restores a
    used bus to the fresh-bus zeros — a restored every-k engine then
    counts from scratch instead of inheriting garbage."""
    from repro.checkpoint import restore_federation, save_federation
    from repro.core.policies import as_policy
    fed = _tiny_fed()
    save_federation(str(tmp_path), fed, step=0)     # no bus section
    fed2 = _tiny_fed()
    bus2 = ServerBus(fed2, as_policy(sqmd(q=4, k=2)),
                     trigger=EveryKUploads(k=2), backend="jnp")
    bus2.deliver(0.0, _msg(0), np.ones(4, bool))    # dirty the counters
    restore_federation(str(tmp_path), fed2, bus=bus2)
    assert bus2.uploads_since_fire == 0
    assert not bus2.fresh_since_fire.any()
    assert bus2.n_uploads == 0 and bus2.n_triggers == 0
    assert np.isinf(bus2.last_upload_t).all()
    assert bus2.bytes_up.sum() == 0
    one = np.zeros(4, bool)
    one[3] = True
    assert not bus2.deliver(1.0, _msg(1), one)      # 1/2: must NOT fire
    assert bus2.deliver(2.0, _msg(2), np.ones(4, bool))


# --- async regimes end-to-end ---------------------------------------------

def test_async_straggler_latency_regime(setup_small):
    """Slow clients' messengers arrive late but ARE merged: their rows
    leave the uniform init, and eval-time staleness shows their lag."""
    ds, splits, zoo, assignment = setup_small
    proc = StragglerLatency(fraction=0.5, delay=2.0, seed=1)
    engine = AsyncFederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4), arrivals=proc,
        config=FederationConfig(**CFG), seed=3)
    h = engine.fit(splits, until=4.0)
    assert np.isfinite(h.mean_acc).all()
    slow = proc.slow_mask(ds.n_clients)
    uniform = -np.log(ds.n_classes)
    repo = np.asarray(engine.server.repo_logp)
    for i in np.where(slow)[0]:
        assert not np.allclose(repo[i], uniform), \
            f"slow client {i}'s delayed upload was dropped"
    # slow rows lag by the upload delay: produced at t-2 when merged
    assert max(s["max"] for s in h.staleness) >= 2.0
    assert engine.bus.n_uploads > 0


def test_async_bursty_arrivals_regime(setup_small):
    """Bursty arrivals + every-k: the server batches uploads across
    bursts and fires fewer policy rounds than deliveries."""
    ds, splits, zoo, assignment = setup_small
    engine = AsyncFederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        arrivals=BurstyArrivals(burst_every=2.0, frac=0.5, jitter=0.8,
                                seed=2),
        trigger=EveryKUploads(k=10),
        config=FederationConfig(**CFG), seed=3)
    h = engine.fit(splits, until=8.0)
    assert np.isfinite(h.mean_acc).all()
    assert engine.bus.n_triggers >= 1
    assert engine.bus.n_triggers <= engine.bus.n_uploads // 10
    assert h.server_rounds == sorted(h.server_rounds)   # monotone counts
    assert all(s["n"] >= 0 for s in h.staleness)


def test_async_quorum_trigger_regime(setup_small):
    """Quorum-triggered server rounds: policy fires only when half the
    federation has freshly uploaded; stale rows still feed the graph."""
    ds, splits, zoo, assignment = setup_small
    engine = AsyncFederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        arrivals=StragglerLatency(fraction=0.5, delay=2.0, seed=1),
        trigger=Quorum(frac=0.5),
        config=FederationConfig(**CFG), seed=3)
    h = engine.fit(splits, until=4.0)
    assert np.isfinite(h.mean_acc).all()
    need = Quorum(frac=0.5).needed(ds.n_clients)
    assert engine.bus.n_triggers <= engine.bus.n_uploads // need
    assert engine.bus.n_triggers >= 1


def test_async_wall_interval_and_resume(setup_small):
    """WallInterval fires on the virtual-time grid, and fit() can be
    called again with a larger horizon to continue the same run."""
    ds, splits, zoo, assignment = setup_small
    engine = AsyncFederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        arrivals=HeterogeneousCadence(fast=1.0, slow=3.0, seed=4),
        trigger=WallInterval(period=2.0),
        config=FederationConfig(**CFG), seed=3)
    h = engine.fit(splits, until=4.0)
    n_evals, n_triggers = len(h.times), engine.bus.n_triggers
    assert n_triggers <= 4.0 / 2.0 + 1
    h = engine.fit(splits, until=8.0)          # continue, don't restart
    assert len(h.times) > n_evals
    assert engine.bus.n_triggers >= n_triggers
    assert h.times == sorted(h.times)
    assert np.isfinite(h.mean_acc).all()


def test_async_fit_smaller_horizon_does_not_reseed(setup_small):
    """A fit() call with a smaller horizon than a prior call is a no-op
    for seeding: it must not replay already-run events on the next
    larger-horizon call."""
    ds, splits, zoo, assignment = setup_small
    engine = AsyncFederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        arrivals=BurstyArrivals(burst_every=2.0, frac=0.5, seed=2),
        config=FederationConfig(**CFG), seed=3)
    engine.fit(splits, until=6.0)
    uploads = engine.bus.n_uploads
    engine.fit(splits, until=2.0)          # smaller horizon: no re-seed
    assert engine.bus.n_uploads == uploads
    h = engine.fit(splits, until=8.0)      # continues without replaying
    assert engine.bus.n_uploads >= uploads
    assert h.times == sorted(h.times)
    assert np.isfinite(h.mean_acc).all()


def test_async_reference_free_policy(setup_small):
    """isgd (no messengers) still trains under the event loop: no uploads,
    no triggers, finite metrics."""
    ds, splits, zoo, assignment = setup_small
    engine = AsyncFederationEngine.build(
        ds, splits, zoo, assignment, isgd(),
        arrivals=BurstyArrivals(burst_every=2.0, frac=0.5, seed=5),
        config=FederationConfig(**CFG), seed=3)
    h = engine.fit(splits, until=4.0)
    assert np.isfinite(h.mean_acc).all()
    assert engine.bus.n_uploads == 0 and engine.bus.n_triggers == 0


# --- History metrics & precision_recall (satellite coverage) --------------

def _hist(mean_acc, val_acc):
    return History(rounds=list(range(len(mean_acc))),
                   mean_acc=list(mean_acc),
                   per_client_acc=[np.full(3, a) for a in mean_acc],
                   val_acc=list(val_acc))


def test_history_selects_best_round_by_validation():
    h = _hist([0.5, 0.9, 0.7], [0.4, 0.8, 0.6])
    assert h.best_round_idx == 1            # argmax of VAL, not test
    assert h.selected_acc == 0.9
    np.testing.assert_array_equal(h.selected_per_client(), np.full(3, 0.9))


def test_history_empty_val_falls_back_to_last_round():
    h = _hist([0.5, 0.9, 0.7], [])
    assert h.best_round_idx == 2
    assert h.selected_acc == 0.7
    assert h.final_metrics()["acc"] == pytest.approx(0.7)


def test_history_val_selection_differs_from_test_argmax():
    # test-acc argmax is round 1, val argmax round 2: val must win
    h = _hist([0.5, 0.9, 0.7], [0.4, 0.6, 0.8])
    assert h.best_round_idx == 2
    assert h.selected_acc == 0.7


def test_precision_recall_constant_predictor():
    """Hand-checkable macro precision/recall: a cohort that always
    predicts class 0."""
    n_classes = 3
    apply_fn = lambda p, x: jnp.tile(  # noqa: E731
        jnp.array([5.0, 0.0, 0.0]), (x.shape[0], 1))
    coh = Cohort(family_name="const", apply_fn=apply_fn,
                 params=jnp.zeros((2, 1)), opt_state=None,
                 client_ids=np.array([0, 1]),
                 data={})
    ys = np.array([[0, 0, 1, 2], [0, 1, 1, 2]])
    splits = [types.SimpleNamespace(test_x=np.zeros((4, 5), np.float32),
                                    test_y=ys[i]) for i in range(2)]
    fed = Federation(cohorts=[coh], server=init_server(2, 4, n_classes),
                     protocol=isgd(), ref_x=jnp.zeros((4, 5)),
                     ref_y=jnp.zeros(4), optimizer=sgd(0.1), n_clients=2)
    prec, rec = precision_recall(fed, splits, n_classes)
    # 8 preds of class 0; 3 true class-0 hits => prec0=3/8, rec0=1;
    # classes 1,2 never predicted => prec=0, rec=0
    assert prec == pytest.approx((3 / 8) / 3)
    assert rec == pytest.approx(1 / 3)


def test_set_default_backend_rejects_unknown():
    from repro.kernels import backend as kb
    from repro.kernels import ops
    before = kb._DEFAULT_BACKEND
    try:
        with pytest.raises(ValueError, match="unknown backend"):
            ops.set_default_backend("cuda")
        ops.set_default_backend("jnp")
        assert ops.default_backend() == "jnp"
    finally:
        kb._DEFAULT_BACKEND = before


def test_kernel_backend_env_override(monkeypatch):
    from repro.kernels import backend as kb
    monkeypatch.setattr(kb, "_DEFAULT_BACKEND", None)
    monkeypatch.setenv(kb.ENV_VAR, "interpret")
    assert kb.default_backend() == "interpret"
    assert kb.default_interpret() is True
    assert kb.resolve_interpret(None) is True
    assert kb.resolve_interpret(False) is False
    monkeypatch.setattr(kb, "_DEFAULT_BACKEND", None)
    monkeypatch.setenv(kb.ENV_VAR, "pallas")
    assert kb.default_backend() == "pallas"
    assert kb.default_interpret() is False
    monkeypatch.setattr(kb, "_DEFAULT_BACKEND", None)
    monkeypatch.setenv(kb.ENV_VAR, "cuda")
    with pytest.raises(ValueError, match="not a valid backend"):
        kb.default_backend()
