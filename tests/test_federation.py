"""Integration tests: end-to-end federation behaviour (Algorithm 1)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (FederationConfig, FederationEngine, evaluate, sqmd,
                        isgd, fedmd, ddist, selection_matrix)
from repro.data import make_splits, pad_like, sc_like
from repro.models.mlp import hetero_mlp_zoo


@pytest.fixture(scope="module")
def setup():
    ds = pad_like(samples_per_client=80, ref_size=60)
    splits = make_splits(ds, seed=0)
    zoo = hetero_mlp_zoo(ds.feature_len, ds.n_classes)
    assignment = [list(zoo)[i % 3] for i in range(ds.n_clients)]
    return ds, splits, zoo, assignment


def _build(setup, proto, seed, rounds=3, batch_size=8, eval_every=10,
           join_round=None):
    ds, splits, zoo, assignment = setup
    return FederationEngine.build(
        ds, splits, zoo, assignment, proto,
        config=FederationConfig(rounds=rounds, batch_size=batch_size,
                                eval_every=eval_every),
        seed=seed, join_round=join_round)


def test_federation_improves_over_init(setup):
    ds, splits, zoo, assignment = setup
    engine = _build(setup, sqmd(q=12, k=4, rho=0.5), seed=1, rounds=15,
                    batch_size=16, eval_every=14)
    acc0 = evaluate(engine.fed, splits).mean()
    hist = engine.fit(splits)
    assert hist.mean_acc[-1] > acc0 + 0.05


def test_heterogeneous_cohorts_exist(setup):
    ds, splits, zoo, assignment = setup
    fed = _build(setup, sqmd(), seed=1).fed
    assert len(fed.cohorts) == 3
    sizes = {c.family_name: c.n_clients for c in fed.cohorts}
    assert sum(sizes.values()) == ds.n_clients
    # different architectures => different param tree shapes
    shapes = [tuple(x.shape for x in jax.tree.leaves(c.params))
              for c in fed.cohorts]
    assert len({len(s) for s in shapes}) > 1 or shapes[0] != shapes[1]


@pytest.mark.parametrize("make_proto", [sqmd, fedmd,
                                        lambda: ddist(k=4), isgd])
def test_all_protocols_run(setup, make_proto):
    ds, splits, zoo, assignment = setup
    engine = _build(setup, make_proto(), seed=2)
    for rnd in range(3):
        engine.run_round(rnd)
    acc = evaluate(engine.fed, splits)
    assert acc.shape == (ds.n_clients,)
    assert np.isfinite(acc).all()


def test_async_join_schedule(setup):
    """Clients joining later must not train or pollute the graph before
    their join round."""
    ds, splits, zoo, assignment = setup
    n = ds.n_clients
    join = [0] * (n - 6) + [5] * 6          # last 6 clients join at round 5
    engine = _build(setup, sqmd(q=10, k=4, rho=0.5), seed=3, rounds=8,
                    join_round=join)
    fed = engine.fed
    late_ids = [i for i in range(n) if join[i] == 5]
    before = {c.family_name: jax.tree.map(lambda x: np.asarray(x).copy(),
                                          c.params) for c in fed.cohorts}
    for rnd in range(3):
        engine.run_round(rnd)
    # late clients' params untouched during rounds 0-2
    for c in fed.cohorts:
        rows = [i for i, cid in enumerate(c.client_ids) if cid in late_ids]
        for r in rows:
            for a, b in zip(jax.tree.leaves(before[c.family_name]),
                            jax.tree.leaves(c.params)):
                np.testing.assert_allclose(np.asarray(a)[r],
                                           np.asarray(b)[r], atol=1e-7)
    # graph never selects un-joined clients as neighbors
    w = np.asarray(selection_matrix(engine.last_graph))
    assert np.allclose(w[:, late_ids], 0.0)
    # after joining they start moving
    for rnd in range(5, 8):
        engine.run_round(rnd)
    moved = False
    for c in fed.cohorts:
        rows = [i for i, cid in enumerate(c.client_ids) if cid in late_ids]
        for r in rows:
            for a, b in zip(jax.tree.leaves(before[c.family_name]),
                            jax.tree.leaves(c.params)):
                if np.abs(np.asarray(a)[r] - np.asarray(b)[r]).max() > 0:
                    moved = True
    assert moved


def test_messengers_only_cross_cohorts(setup):
    """Privacy contract: the server state contains no model parameters and
    no raw training samples — only (N,R,C) soft decisions + scalars."""
    ds, splits, zoo, assignment = setup
    engine = _build(setup, sqmd(), seed=4)
    engine.run_round(0)
    fed = engine.fed
    n, r, c = fed.server.repo_logp.shape
    assert (n, r, c) == (ds.n_clients, len(ds.ref_y), ds.n_classes)
    leaves = jax.tree.leaves(fed.server._asdict())
    total_floats = sum(x.size for x in leaves)
    # server state is O(N*R*C + N^2), strictly smaller than any cohort's
    # parameter count
    params_floats = sum(x.size for x in jax.tree.leaves(
        fed.cohorts[-1].params))
    assert total_floats < params_floats


def test_checkpoint_roundtrip(tmp_path, setup):
    from repro.checkpoint import restore_federation, save_federation
    ds, splits, zoo, assignment = setup
    engine = _build(setup, sqmd(), seed=5)
    for rnd in range(2):
        engine.run_round(rnd)
    acc_before = evaluate(engine.fed, splits)
    save_federation(str(tmp_path), engine.fed, step=2)

    fed2 = _build(setup, sqmd(), seed=99).fed
    step = restore_federation(str(tmp_path), fed2)
    assert step == 2
    acc_after = evaluate(fed2, splits)
    np.testing.assert_allclose(acc_before, acc_after, atol=1e-6)
    # the wire codec names round-trip with the state
    assert fed2.uplink == "dense32" and fed2.downlink == "dense32"


def test_checkpoint_resume_equivalence(tmp_path, setup):
    """A run interrupted by save/restore must continue EXACTLY like the
    uninterrupted run: rng, distill targets, and the bus's trigger
    bookkeeping all resume (a restored engine used to re-derive its RNG
    and drop the targets, silently forking the trajectory)."""
    from repro.checkpoint import restore_federation, save_federation
    ds, splits, zoo, assignment = setup

    oracle = _build(setup, sqmd(q=10, k=4), seed=11, rounds=4)
    for rnd in range(4):
        oracle.run_round(rnd)

    first = _build(setup, sqmd(q=10, k=4), seed=11, rounds=4)
    for rnd in range(2):
        first.run_round(rnd)
    save_federation(str(tmp_path), first.fed, step=2, bus=first.bus)

    resumed = _build(setup, sqmd(q=10, k=4), seed=77, rounds=4)  # other seed
    restore_federation(str(tmp_path), resumed.fed, bus=resumed.bus)
    for rnd in range(2, 4):
        resumed.run_round(rnd)

    np.testing.assert_allclose(evaluate(resumed.fed, splits),
                               evaluate(oracle.fed, splits), atol=1e-7)
    np.testing.assert_allclose(np.asarray(selection_matrix(
                                   resumed.last_graph)),
                               np.asarray(selection_matrix(
                                   oracle.last_graph)),
                               atol=1e-7)
    assert resumed.bus.n_triggers == oracle.bus.n_triggers
    np.testing.assert_allclose(resumed.bus.bytes_up, oracle.bus.bytes_up)
