"""Tests for the pluggable-policy API, availability schedules, and the
FederationEngine (registry round-trips, schedule parity, and a toy policy
running end-to-end with zero core changes)."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AlwaysOn, Federation, FederationConfig,
                        FederationEngine, Protocol, RandomDropout,
                        ServerPolicy, StagedJoin, Straggler, evaluate,
                        fedmd, get_policy, get_schedule, graph_stats,
                        init_server, isgd, precision_recall,
                        register_policy, registered_policies, server_round,
                        sqmd, upload_messengers)
from repro.core.graph import CollaborationGraph
from repro.core.policies import SQMDPolicy, as_policy, unregister_policy
from repro.data import make_splits, pad_like
from repro.models.mlp import hetero_mlp_zoo


@pytest.fixture(scope="module")
def setup():
    # deliberately small (CI speed): these tests assert wiring/parity, not
    # learning quality — the parity tests compare both drivers on the SAME
    # fixture, so the scale is free to shrink
    ds = pad_like(samples_per_client=16, ref_size=16, length=16)
    splits = make_splits(ds, seed=0)
    zoo = hetero_mlp_zoo(ds.feature_len, ds.n_classes)
    assignment = [list(zoo)[i % 3] for i in range(ds.n_clients)]
    return ds, splits, zoo, assignment


# --- policy registry ------------------------------------------------------

def test_registry_roundtrip():
    assert set(registered_policies()) >= {"sqmd", "fedmd", "ddist", "isgd"}
    assert get_policy("sqmd") is SQMDPolicy
    pol = as_policy(sqmd(q=5, k=3))
    assert isinstance(pol, SQMDPolicy)
    assert pol.protocol.q == 5 and pol.name == "sqmd"
    assert isinstance(as_policy("fedmd"), get_policy("fedmd"))


def test_registry_unknown_name_errors():
    with pytest.raises(KeyError, match="unknown policy"):
        get_policy("no-such-policy")
    with pytest.raises(ValueError, match="unknown protocol"):
        Protocol("no-such-policy")


def test_registry_rejects_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        @register_policy("sqmd")
        class Clone(ServerPolicy):  # pragma: no cover - never registered
            def build_graph(self, state, quality):
                raise NotImplementedError


def test_protocol_validation_raises_valueerror():
    # ValueError (not AssertionError) so python -O still rejects bad configs
    with pytest.raises(ValueError, match="rho"):
        Protocol("sqmd", rho=1.5)
    with pytest.raises(ValueError, match="q must"):
        Protocol("sqmd", q=0)
    with pytest.raises(ValueError, match="interval"):
        Protocol("sqmd", interval=0)


# --- availability schedules -----------------------------------------------

def test_schedule_registry():
    assert get_schedule("dropout") is RandomDropout
    with pytest.raises(KeyError, match="unknown schedule"):
        get_schedule("no-such-schedule")


def test_always_on_schedule():
    s = AlwaysOn()
    assert s.available(0, 7).all() and s.joined(100, 7).all()


def test_staged_join_schedule():
    s = StagedJoin([0, 0, 5, 9])
    np.testing.assert_array_equal(s.available(0, 4),
                                  [True, True, False, False])
    np.testing.assert_array_equal(s.available(5, 4),
                                  [True, True, True, False])
    assert s.available(9, 4).all()
    with pytest.raises(ValueError, match="entries"):
        s.available(0, 6)


def test_dropout_schedule_deterministic_and_bounded():
    s = RandomDropout(p=0.4, seed=3)
    masks = [s.available(r, 50) for r in range(20)]
    # deterministic given (seed, round)
    np.testing.assert_array_equal(masks[7], s.available(7, 50))
    # roughly the requested availability rate
    rate = np.mean([m.mean() for m in masks])
    assert 0.4 < rate < 0.8
    # at least one client always available; joined is everyone
    assert all(m.any() for m in masks)
    assert s.joined(0, 50).all()
    # composes over a base schedule: never available before joining
    comp = RandomDropout(p=0.4, seed=3, base=StagedJoin([0] * 25 + [9] * 25))
    assert not comp.available(2, 50)[25:].any()
    with pytest.raises(ValueError, match="dropout p"):
        RandomDropout(p=1.0)


def test_straggler_schedule():
    s = Straggler(fraction=0.5, period=4, seed=1)
    slow = s.slow_mask(20)
    assert slow.sum() == 10
    # stragglers participate only on period rounds
    np.testing.assert_array_equal(s.available(4, 20), np.ones(20, bool))
    off = s.available(5, 20)
    np.testing.assert_array_equal(off, ~slow)
    assert s.joined(5, 20).all()


# --- policy-agnostic server round ----------------------------------------

def _uploaded_server(n=6, r=12, c=3, seed=0):
    labels = jax.random.randint(jax.random.key(seed), (r,), 0, c)
    logp = jax.nn.log_softmax(
        jax.random.normal(jax.random.key(seed + 1), (n, r, c)) * 2, -1)
    st = init_server(n, r, c)
    return upload_messengers(st, logp, jnp.ones((n,), bool)), labels


def test_server_round_accepts_policy_instance_and_name():
    st, labels = _uploaded_server()
    by_name = server_round(st, "fedmd", labels)
    by_inst = server_round(st, as_policy(fedmd()), labels)
    np.testing.assert_allclose(np.asarray(by_name[1]),
                               np.asarray(by_inst[1]), atol=1e-7)


def test_server_round_ignores_static_weights_for_graphless_policies():
    """Legacy contract: only static-graph policies consume the argument."""
    st, labels = _uploaded_server()
    n = st.active.shape[0]
    w = jnp.ones((n, n)) / n
    plain = server_round(st, fedmd(), labels)
    with_w = server_round(st, fedmd(), labels, static_weights=w)
    np.testing.assert_allclose(np.asarray(plain[1]), np.asarray(with_w[1]),
                               atol=1e-7)


# --- a toy policy: end-to-end with zero core modifications ---------------

@pytest.fixture()
def toy_policy():
    @register_policy("toy-best")
    class ToyBestPolicy(ServerPolicy):
        """Everyone distills toward the single best-graded messenger."""

        def build_graph(self, state, quality, *, backend=None):
            n = state.active.shape[0]
            best = jnp.argmin(jnp.where(state.active, quality, jnp.inf))
            w = jnp.zeros((n, n), jnp.float32).at[:, best].set(1.0)
            w = w * state.active[:, None]          # only members receive
            return CollaborationGraph(
                neighbors=jnp.tile(best[None, None], (n, 1)).astype(jnp.int32),
                weights=w, candidates=state.active)

    yield ToyBestPolicy
    unregister_policy("toy-best")


def test_toy_policy_end_to_end(setup, toy_policy):
    """Acceptance: a new policy runs through server_round AND the engine
    without touching core/server.py or core/engine.py."""
    st, labels = _uploaded_server()
    st2, targets = server_round(st, Protocol("toy-best"), labels)
    best = int(np.argmin(np.asarray(st2.quality)))
    # every client's target row equals the best client's messenger
    best_msgr = np.asarray(jnp.exp(st.repo_logp[best]))
    np.testing.assert_allclose(
        np.asarray(targets), np.broadcast_to(best_msgr, targets.shape),
        atol=1e-5)

    ds, splits, zoo, assignment = setup
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, "toy-best",
        config=FederationConfig(rounds=3, batch_size=8, eval_every=2))
    hist = engine.fit(splits)
    assert np.isfinite(hist.mean_acc).all()
    assert engine.last_graph is not None


# --- the engine -----------------------------------------------------------

def test_config_validation():
    with pytest.raises(ValueError, match="batch_size"):
        FederationConfig(batch_size=0)
    with pytest.raises(ValueError, match="eval_every"):
        FederationConfig(eval_every=0)


def test_legacy_federation_module_is_gone():
    """The deprecation shims were deleted: the engine is the only API."""
    import repro.core
    assert not hasattr(repro.core, "build_federation")
    assert not hasattr(repro.core, "train_federation")
    with pytest.raises(ImportError):
        import repro.core.federation  # noqa: F401


def test_engine_backend_threading(setup, monkeypatch):
    """The process-level kernel setting reaches the server kernels: an
    engine under the Pallas interpreter trains as one on the jnp oracle."""
    from repro.kernels.backend import ENV_VAR
    ds, splits, zoo, assignment = setup
    accs = []
    for backend in ("jnp", "interpret"):
        monkeypatch.setenv(ENV_VAR, backend)
        engine = FederationEngine.build(
            ds, splits, zoo, assignment, sqmd(q=8, k=4),
            config=FederationConfig(rounds=2, batch_size=8, eval_every=1),
            seed=3)
        accs.append(engine.fit(splits).mean_acc)
    np.testing.assert_allclose(accs[0], accs[1], atol=1e-4)


def test_engine_real_graph_stats(setup):
    """History carries stats of the policy's ACTUAL graph: the candidate
    count is the top-Q pool, not a placeholder active mask."""
    ds, splits, zoo, assignment = setup
    q = 6
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=q, k=3),
        config=FederationConfig(rounds=2, batch_size=8, eval_every=1))
    hist = engine.fit(splits)
    assert hist.graph_stats, "no graph stats recorded"
    assert hist.graph_stats[-1]["n_candidates"] == q
    assert hist.graph_stats[-1]["out_degree"] == pytest.approx(3.0)
    np.testing.assert_array_equal(
        np.asarray(graph_stats(engine.last_graph)["n_candidates"]), q)


def test_engine_callbacks_fire_at_eval_cadence(setup):
    ds, splits, zoo, assignment = setup
    seen = []
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, isgd(),
        config=FederationConfig(rounds=5, batch_size=8, eval_every=2),
        callbacks=[lambda eng, rnd, m: seen.append((rnd, m["acc"]))])
    engine.fit(splits)
    assert [r for r, _ in seen] == [0, 2, 4]
    assert all(np.isfinite(a) for _, a in seen)


@pytest.mark.parametrize("schedule", [
    RandomDropout(p=0.3, seed=2),
    Straggler(fraction=0.4, period=2, seed=2),
])
def test_engine_runs_under_flaky_schedules(setup, schedule):
    """One test per new availability schedule: training proceeds, metrics
    stay finite, and unavailable clients are frozen for the round."""
    ds, splits, zoo, assignment = setup
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(rounds=3, batch_size=8, eval_every=2),
        schedule=schedule, seed=4)
    before = {c.family_name: jax.tree.map(lambda x: np.asarray(x).copy(),
                                          c.params)
              for c in engine.fed.cohorts}
    engine.run_round(1)  # round 1: both schedules have unavailable clients
    off = ~np.asarray(schedule.available(1, ds.n_clients), bool)
    assert off.any(), "schedule produced no unavailable clients"
    for c in engine.fed.cohorts:
        rows = [i for i, cid in enumerate(c.client_ids) if off[cid]]
        for r in rows:
            for a, b in zip(jax.tree.leaves(before[c.family_name]),
                            jax.tree.leaves(c.params)):
                np.testing.assert_allclose(np.asarray(a)[r],
                                           np.asarray(b)[r], atol=1e-7)
    hist = engine.fit(splits)
    assert np.isfinite(hist.mean_acc).all()


def test_engine_staged_join_matches_join_round_arg(setup):
    """A StagedJoin schedule and the ``join_round=`` build argument are
    the same thing: identical same-seed trajectories."""
    ds, splits, zoo, assignment = setup
    n = ds.n_clients
    join = [0] * (n - 6) + [2] * 6
    cfg = dict(rounds=3, batch_size=8, eval_every=2)
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(**cfg), schedule=StagedJoin(join), seed=5)
    h_new = engine.fit(splits)
    other = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(**cfg), join_round=join, seed=5)
    h_old = other.fit(splits)
    np.testing.assert_allclose(h_new.mean_acc, h_old.mean_acc, atol=1e-7)


# --- evaluate / precision_recall with unequal shards (regression) ---------

def _const_predictor_fed(test_lens, n_classes=3):
    """One cohort of constant class-0 predictors with UNEQUAL test shards:
    exact accuracy/precision arithmetic by hand."""
    apply_fn = lambda p, x: jnp.tile(  # noqa: E731
        jnp.array([5.0] + [0.0] * (n_classes - 1)), (x.shape[0], 1))
    n = len(test_lens)
    coh = types.SimpleNamespace(
        family_name="const", apply_fn=apply_fn,
        params=jnp.zeros((n, 1)), opt_state=None,
        client_ids=np.arange(n), n_clients=n, data={})
    rng = np.random.default_rng(0)
    splits = []
    for m in test_lens:
        ys = np.arange(m) % n_classes          # class 0 hit every n_classes
        splits.append(types.SimpleNamespace(
            test_x=rng.normal(size=(m, 4)).astype(np.float32),
            test_y=ys))
    from repro.optim import sgd
    fed = Federation(cohorts=[coh], server=init_server(n, 4, n_classes),
                     protocol=isgd(), ref_x=jnp.zeros((4, 4)),
                     ref_y=jnp.zeros(4), optimizer=sgd(0.1), n_clients=n)
    return fed, splits


def test_evaluate_unequal_shards_drops_no_samples():
    """Regression: evaluate() used to truncate every cohort shard to the
    SHORTEST client's length — a client with 9 samples (3 of class 0) was
    scored on its first 4 only."""
    fed, splits = _const_predictor_fed([4, 9])
    acc = evaluate(fed, splits)
    # exact per-client means over the FULL shards: ceil(m/3)/m class-0 hits
    np.testing.assert_allclose(acc, [2 / 4, 3 / 9], atol=1e-6)


def test_precision_recall_unequal_shards_counts_everything():
    fed, splits = _const_predictor_fed([4, 9], n_classes=3)
    prec, rec = precision_recall(fed, splits, 3)
    # 13 predictions of class 0; true class-0 count = 2 + 3 = 5
    assert prec == pytest.approx((5 / 13) / 3)
    assert rec == pytest.approx(1 / 3)


def test_evaluate_equal_shards_unchanged():
    """Equal lengths keep the original unmasked path (bit-exact with the
    pinned trajectories) and agree with the masked arithmetic."""
    fed, splits = _const_predictor_fed([6, 6])
    np.testing.assert_allclose(evaluate(fed, splits), [2 / 6, 2 / 6],
                               atol=1e-6)
