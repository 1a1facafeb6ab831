"""Device-sharded cohort execution over the client axis.

Single-device tests (ghost-pad semantics, mesh construction, config
validation) always run; the mesh-parity tests need >= 8 devices and run
in the CI sharded lane via

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m pytest tests/test_client_sharding.py

(the flag must be set BEFORE jax imports, so they skip in the default
single-device tier-1 run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AsyncFederationEngine, FederationConfig,
                        FederationEngine, Quorum, StragglerLatency, sqmd)
from repro.core.client import cohort_step
from repro.data import make_splits, pad_like
from repro.data.pipeline import cohort_batch, cohort_batch_padded
from repro.models.mlp import hetero_mlp_zoo
from repro.optim import sgd
from repro.sharding import (CLIENT_AXIS, client_sharding, ghost_pad_stack,
                            ghost_rows, make_client_mesh)

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(CI sharded lane)")


@pytest.fixture(scope="module")
def setup_small():
    ds = pad_like(samples_per_client=16, ref_size=16, length=16)
    splits = make_splits(ds, seed=0)
    zoo = hetero_mlp_zoo(ds.feature_len, ds.n_classes)
    assignment = [list(zoo)[i % 3] for i in range(ds.n_clients)]
    return ds, splits, zoo, assignment


CFG = dict(rounds=4, batch_size=8, eval_every=2)


# --- helpers / semantics (single-device) ----------------------------------

def test_ghost_rows_padding_arithmetic():
    assert ghost_rows(10, 8) == 6
    assert ghost_rows(16, 8) == 0
    assert ghost_rows(3, 8) == 5
    assert ghost_rows(7, 1) == 0


def test_ghost_pad_stack_replicates_last_row():
    tree = {"a": jnp.arange(6.0).reshape(3, 2), "b": jnp.arange(3)}
    padded = ghost_pad_stack(tree, 2)
    assert padded["a"].shape == (5, 2)
    np.testing.assert_array_equal(padded["a"][3], padded["a"][2])
    np.testing.assert_array_equal(padded["b"][-2:], [2, 2])
    assert ghost_pad_stack(tree, 0) is tree


def test_make_client_mesh_validates_device_count():
    mesh = make_client_mesh(1)
    assert mesh.axis_names == (CLIENT_AXIS,)
    assert mesh.shape[CLIENT_AXIS] == 1
    too_many = jax.device_count() + 1
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        make_client_mesh(too_many)
    with pytest.raises(ValueError, match="n_dev"):
        make_client_mesh(0)


def test_config_validates_devices():
    with pytest.raises(ValueError, match="devices"):
        FederationConfig(devices=0)
    assert FederationConfig(devices=1).devices == 1
    assert FederationConfig().devices is None


def test_cohort_batch_padded_draws_match_unpadded():
    """The padded sampler must consume the identical RNG values for real
    rows (threefry depends on the requested shape, so drawing at the
    padded size would silently change every client's batches)."""
    key = jax.random.key(3)
    data = {"x": jax.random.normal(jax.random.key(0), (5, 12, 4)),
            "y": jax.random.randint(jax.random.key(1), (5, 12), 0, 3)}
    plain = cohort_batch(key, data, 6)
    padded_data = ghost_pad_stack(data, 3)
    padded = cohort_batch_padded(key, padded_data, 6, 5)
    np.testing.assert_array_equal(np.asarray(plain["x"]),
                                  np.asarray(padded["x"][:5]))
    np.testing.assert_array_equal(np.asarray(plain["y"]),
                                  np.asarray(padded["y"][:5]))
    # ghost rows replicate the last real client's batch
    np.testing.assert_array_equal(np.asarray(padded["y"][5]),
                                  np.asarray(padded["y"][4]))


def test_ghost_rows_are_bitexact_noops_single_device(setup_small):
    """A ghost-padded cohort step with the ghosts masked out advances the
    real rows bit-for-bit like the unpadded step (the PR 3 frozen-client
    guarantee is what makes device padding safe)."""
    ds, splits, zoo, assignment = setup_small
    engine = FederationEngine.build(ds, splits, zoo, assignment,
                                    sqmd(q=8, k=4),
                                    config=FederationConfig(**CFG), seed=0)
    coh = engine.fed.cohorts[0]
    n_c, pad = coh.n_clients, 3
    opt = engine.fed.optimizer
    ref_x = engine.fed.ref_x
    r, c = ref_x.shape[0], ds.n_classes
    targets = jnp.full((n_c, r, c), 1.0 / c)
    key = jax.random.key(9)
    batch = cohort_batch(key, coh.data, 8)

    p1, s1, l1 = cohort_step(coh.apply_fn, opt, coh.params, coh.opt_state,
                             batch["x"], batch["y"], ref_x, targets,
                             jnp.ones((n_c,), bool), 0.5, True)
    pp, sp, lp = cohort_step(
        coh.apply_fn, opt,
        ghost_pad_stack(coh.params, pad), ghost_pad_stack(coh.opt_state,
                                                          pad),
        ghost_pad_stack(batch["x"], pad), ghost_pad_stack(batch["y"], pad),
        ref_x, ghost_pad_stack(targets, pad),
        jnp.concatenate([jnp.ones((n_c,), bool), jnp.zeros((pad,), bool)]),
        0.5, True)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:n_c])
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(sp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:n_c])
    # ghost params did not move (every ghost row still == the last real
    # client's ORIGINAL params)
    for orig, stepped in zip(jax.tree.leaves(coh.params),
                             jax.tree.leaves(pp)):
        assert (np.asarray(stepped)[n_c:] == np.asarray(orig)[-1]).all()


def test_devices_one_matches_legacy_path(setup_small):
    """devices=1 goes through the mesh machinery (pad=0) and must stay
    bit-identical to the devices=None legacy path."""
    ds, splits, zoo, assignment = setup_small
    h_legacy = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(**CFG), seed=5).fit(splits)
    h_mesh = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(**CFG, devices=1), seed=5).fit(splits)
    np.testing.assert_allclose(h_mesh.mean_acc, h_legacy.mean_acc,
                               rtol=0, atol=0)
    np.testing.assert_allclose(h_mesh.val_acc, h_legacy.val_acc,
                               rtol=0, atol=0)


# --- mesh parity (CI sharded lane: 8 fake host devices) -------------------

# The n_dev=1 oracle trajectory, pinned in
# tests/test_runtime.py::test_sync_parity_pinned on exactly the
# pad_like(30, 30, 24) fixture below. The n_dev=8 run must reproduce it.
from test_runtime import PINNED_MEAN_ACC, PINNED_VAL_ACC  # noqa: E402


@needs_mesh
def test_sharded_sync_matches_pinned_oracle():
    """ACCEPTANCE: the n_dev=8 sync engine reproduces the pinned n_dev=1
    oracle trajectory (cohorts of 10 pad to 16 ghost rows here)."""
    ds = pad_like(samples_per_client=30, ref_size=30, length=24)
    splits = make_splits(ds, seed=0)
    zoo = hetero_mlp_zoo(ds.feature_len, ds.n_classes)
    assignment = [list(zoo)[i % 3] for i in range(ds.n_clients)]
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(**CFG, devices=8), seed=7)
    h = engine.fit(splits)
    for coh in engine.fed.cohorts:        # padding really engaged
        assert coh.n_pad == ghost_rows(coh.n_clients, 8)
        assert coh.n_rows % 8 == 0
    np.testing.assert_allclose(h.mean_acc, PINNED_MEAN_ACC, rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(h.val_acc, PINNED_VAL_ACC, rtol=0,
                               atol=1e-6)


@needs_mesh
def test_sharded_async_matches_single_device(setup_small):
    """The async engine under straggler latency + quorum trigger: n_dev=8
    matches the single-device run (same wire bytes, same trajectory)."""
    ds, splits, zoo, assignment = setup_small

    def run(devices):
        eng = AsyncFederationEngine.build(
            ds, splits, zoo, assignment, sqmd(q=8, k=4),
            arrivals=StragglerLatency(fraction=0.5, delay=2.0, seed=1),
            trigger=Quorum(frac=0.5),
            config=FederationConfig(**CFG, devices=devices), seed=3)
        return eng, eng.fit(splits, until=4.0)

    e1, h1 = run(None)
    e8, h8 = run(8)
    np.testing.assert_allclose(h8.mean_acc, h1.mean_acc, rtol=0, atol=1e-6)
    assert h8.bytes_up == h1.bytes_up
    assert h8.server_rounds == h1.server_rounds
    np.testing.assert_allclose(np.asarray(e8.fed.server.repo_logp),
                               np.asarray(e1.fed.server.repo_logp),
                               rtol=0, atol=1e-6)


@needs_mesh
@pytest.mark.parametrize("n", [37, 64])
def test_sharded_divergence_matches_oracle(n):
    """Row-sharded Eq.2 rebuild == single-device oracle, including the
    pad/slice path for repository sizes that don't divide the mesh."""
    from repro.core.similarity import divergence_matrix
    from repro.kernels import ops
    mesh = make_client_mesh(8)
    logp = jax.nn.log_softmax(
        jax.random.normal(jax.random.key(0), (n, 20, 5)) * 2, -1)
    oracle = np.asarray(ops.pairwise_kl(logp, backend="jnp"))
    d = np.asarray(divergence_matrix(logp, mesh=mesh))
    assert d.shape == (n, n)
    np.testing.assert_allclose(d, oracle, rtol=0, atol=1e-6)


@needs_mesh
def test_sharded_policy_graph_matches_oracle():
    """SQMD build_graph with a bus-attached mesh selects the identical
    neighbors as the single-device build."""
    from repro.core import init_server, upload_messengers
    from repro.core.policies import as_policy
    n, r, c = 26, 15, 4
    logp = jax.nn.log_softmax(
        jax.random.normal(jax.random.key(2), (n, r, c)) * 2, -1)
    labels = jax.random.randint(jax.random.key(3), (r,), 0, c)
    state = upload_messengers(init_server(n, r, c), logp,
                              jnp.ones((n,), bool))
    pol1 = as_policy(sqmd(q=8, k=4))
    pol8 = as_policy(sqmd(q=8, k=4))
    pol8.mesh = make_client_mesh(8)
    quality = pol1.grade(state, labels)
    g1 = pol1.build_graph(state, quality)
    g8 = pol8.build_graph(state, quality)
    np.testing.assert_array_equal(np.asarray(g1.neighbors),
                                  np.asarray(g8.neighbors))
    np.testing.assert_allclose(np.asarray(g8.divergence),
                               np.asarray(g1.divergence), rtol=0, atol=1e-6)


@needs_mesh
def test_sharded_stacks_actually_sharded(setup_small):
    """The cohort stacks really live row-sharded on the mesh (not
    replicated): every param leaf's sharding is the client NamedSharding
    and addressable shards hold 1/n_dev of the rows."""
    ds, splits, zoo, assignment = setup_small
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(**CFG, devices=8), seed=0)
    engine.run_round(0)
    for coh in engine.fed.cohorts:
        sh = client_sharding(engine.mesh)
        for leaf in jax.tree.leaves(coh.params):
            assert leaf.sharding == sh
            shard_rows = {s.data.shape[0] for s in leaf.addressable_shards}
            assert shard_rows == {coh.n_rows // 8}


@needs_mesh
def test_sharded_checkpoint_roundtrip(tmp_path, setup_small):
    """Sharded save -> unsharded restore (and back): checkpoint files are
    device-layout-agnostic, real rows only."""
    from repro.checkpoint import restore_federation, save_federation
    ds, splits, zoo, assignment = setup_small
    e8 = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(**CFG, devices=8), seed=5)
    for rnd in range(2):
        e8.run_round(rnd)
    acc8 = e8.evaluate(splits)
    save_federation(str(tmp_path), e8.fed, step=2, bus=e8.bus)

    # restore into an unsharded engine
    e1 = FederationEngine.build(ds, splits, zoo, assignment, sqmd(q=8, k=4),
                                config=FederationConfig(**CFG), seed=99)
    restore_federation(str(tmp_path), e1.fed, bus=e1.bus)
    np.testing.assert_allclose(e1.evaluate(splits), acc8, atol=1e-6)
    assert e1.bus.n_triggers == e8.bus.n_triggers

    # and back into a sharded engine: ghost padding re-applied
    e8b = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(**CFG, devices=8), seed=42)
    restore_federation(str(tmp_path), e8b.fed, bus=e8b.bus)
    for coh in e8b.fed.cohorts:
        assert jax.tree.leaves(coh.params)[0].shape[0] == coh.n_rows
    np.testing.assert_allclose(e8b.evaluate(splits), acc8, atol=1e-6)
    e8b.run_round(2)                      # resumed engine keeps stepping
    assert np.isfinite(e8b.evaluate(splits)).all()
