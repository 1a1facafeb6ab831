"""Tests for the incremental server graph path and this PR's bugfixes:

  * delta-row divergence strips (``pairwise_kl_pair``) and the chunked
    large-N driver vs the monolithic rebuild,
  * ``ServerState.div_cache`` scatter updates vs the full-rebuild oracle,
    threaded end-to-end through policy_round / ServerBus / the engines,
  * frozen clients keep optimizer state bit-for-bit (cohort_step),
  * ``ddist_graph`` sparse-candidate edge cases (zero active clients,
    fewer candidates than k),
  * platform-resolved ``interpret`` defaults for direct kernel callers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FederationConfig, FederationEngine, ServerBus,
                        StagedJoin, divergence_matrix, init_server,
                        policy_round, selection_matrix, sqmd,
                        update_divergence_cache, upload_messengers)
from repro.core.graph import ddist_graph
from repro.core.policies import as_policy
from repro.kernels import ops, ref
from repro.kernels.pairwise_kl import default_interpret, pairwise_kl

from repro.data import make_splits, pad_like
from repro.models.mlp import hetero_mlp_zoo


def _logp(n, r, c, seed=0, sharp=2.0):
    z = jax.random.normal(jax.random.key(seed), (n, r, c)) * sharp
    return jax.nn.log_softmax(z, -1)


# --- strip kernels --------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_pairwise_kl_pair_matches_square(backend):
    lp = _logp(9, 11, 4)
    full = np.asarray(ref.pairwise_kl_ref(lp))
    rows = ops.pairwise_kl_pair(lp[2:5], lp, backend=backend)   # (3, 9)
    cols = ops.pairwise_kl_pair(lp, lp[2:5], backend=backend)   # (9, 3)
    np.testing.assert_allclose(np.asarray(rows), full[2:5], atol=1e-5)
    np.testing.assert_allclose(np.asarray(cols), full[:, 2:5], atol=1e-5)


def test_pairwise_kl_pair_rejects_shape_mismatch():
    from repro.kernels.pairwise_kl import pairwise_kl_pair
    with pytest.raises(ValueError, match="disagree"):
        pairwise_kl_pair(_logp(3, 4, 5), _logp(3, 4, 6))


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_pairwise_kl_chunked_matches_monolithic(backend):
    lp = _logp(10, 8, 3, seed=1)
    full = np.asarray(ref.pairwise_kl_ref(lp))
    chunked = ops.pairwise_kl(lp, backend=backend, row_block=3)
    np.testing.assert_allclose(np.asarray(chunked), full, atol=1e-5)


def test_select_neighbors_traceable_under_jit():
    """The pool fast path needs concrete candidates; under an outer jit
    the dense fallback keeps select_neighbors traceable with identical
    results."""
    from repro.core import select_neighbors, selection_matrix
    lp = _logp(8, 10, 3, seed=3)
    div = divergence_matrix(lp)
    cand = jnp.asarray([True] * 6 + [False] * 2)
    eager = select_neighbors(div, cand, 3)
    jitted = jax.jit(lambda d, c: selection_matrix(
        select_neighbors(d, c, 3)))(div, cand)
    np.testing.assert_allclose(np.asarray(jitted),
                               np.asarray(selection_matrix(eager)),
                               atol=1e-6)


def test_interpret_defaults_from_platform():
    """Direct kernel callers no longer silently run the interpreter on
    TPU: the default is platform-resolved (interpreter off TPU only)."""
    on_tpu = jax.devices()[0].platform == "tpu"
    assert default_interpret() == (not on_tpu)
    lp = _logp(6, 7, 3, seed=2)
    got = pairwise_kl(lp)           # no explicit interpret: platform default
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.pairwise_kl_ref(lp)),
                               atol=1e-5)


# --- div_cache scatter vs full rebuild ------------------------------------

def test_cache_scatter_equals_rebuild_after_upload_sequence():
    n, r, c = 8, 10, 3
    st = init_server(n, r, c)
    cache = st.div_cache
    masks = [np.zeros(n, bool),                         # empty delivery
             np.eye(n, dtype=bool)[3],                  # single row
             np.arange(n) < 5,                          # strip batch
             np.ones(n, bool)]                          # full refresh
    for i, mask in enumerate(masks):
        st = upload_messengers(st, _logp(n, r, c, seed=20 + i),
                               jnp.asarray(mask))
        cache = update_divergence_cache(cache, st.repo_logp, mask)
    oracle = divergence_matrix(st.repo_logp)
    np.testing.assert_allclose(np.asarray(cache), np.asarray(oracle),
                               atol=1e-5)


def test_cache_never_uploaded_rows_stay_exact():
    """The zero-initialized cache IS the divergence of the uniform
    repository: rows nobody ever uploaded need no strip at all."""
    n, r, c = 6, 8, 4
    st = init_server(n, r, c)
    mask = np.arange(n) < 2                 # only clients 0,1 ever upload
    st = upload_messengers(st, _logp(n, r, c, seed=31), jnp.asarray(mask))
    cache = update_divergence_cache(st.div_cache, st.repo_logp, mask)
    oracle = divergence_matrix(st.repo_logp)
    np.testing.assert_allclose(np.asarray(cache), np.asarray(oracle),
                               atol=1e-5)
    # uniform-vs-uniform pairs are exactly zero KL
    assert np.allclose(np.asarray(cache)[2:, 2:], 0.0, atol=1e-6)


def test_policy_round_delta_matches_full_rebuild():
    n, r, c = 7, 10, 3
    labels = jax.random.randint(jax.random.key(1), (r,), 0, c)
    pol = as_policy(sqmd(q=5, k=3))
    st = upload_messengers(init_server(n, r, c), _logp(n, r, c, seed=40),
                           jnp.ones(n, bool))
    st, _, g = policy_round(st, pol, labels)
    np.testing.assert_allclose(np.asarray(st.div_cache),
                               np.asarray(g.divergence))
    # one fresh upload, then delta vs full on identical state
    mask = np.zeros(n, bool)
    mask[4] = True
    st = upload_messengers(st, _logp(n, r, c, seed=41), jnp.asarray(mask))
    st_d, tgt_d, g_d = policy_round(st, pol, labels,
                                    uploaded=mask)
    st_f, tgt_f, g_f = policy_round(st, pol, labels)
    np.testing.assert_allclose(np.asarray(g_d.divergence),
                               np.asarray(g_f.divergence), atol=1e-5)
    np.testing.assert_allclose(np.asarray(selection_matrix(g_d)),
                               np.asarray(selection_matrix(g_f)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(tgt_d), np.asarray(tgt_f),
                               atol=1e-5)
    # the delta round persisted its updated cache
    np.testing.assert_allclose(np.asarray(st_d.div_cache),
                               np.asarray(g_d.divergence))


def test_policy_round_mask_is_optional_for_any_policy():
    """Policies without a delta override (base fallback) accept the mask
    and just rebuild — uploaded=None stays the legacy contract."""
    n, r, c = 6, 8, 3
    labels = jax.random.randint(jax.random.key(2), (r,), 0, c)
    st = upload_messengers(init_server(n, r, c), _logp(n, r, c, seed=50),
                           jnp.ones(n, bool))
    pol = as_policy("fedmd")
    mask = np.arange(n) < 2
    _, t_delta, _ = policy_round(st, pol, labels,
                                 uploaded=mask)
    _, t_full, _ = policy_round(st, pol, labels)
    np.testing.assert_allclose(np.asarray(t_delta), np.asarray(t_full),
                               atol=1e-7)


# --- the K-sparse graph form ----------------------------------------------

def _assert_k_sparse(g, n, k):
    """SQMD's graph form: no (N,N) W, (N,K) edge weights whose realized
    rows sum to 1, and a selection matrix equal bit for bit to the dense
    scatter of those edges."""
    assert g.weights is None
    ew = np.asarray(g.edge_weights)
    nb = np.asarray(g.neighbors)
    assert ew.shape == nb.shape == (n, k) and ew.dtype == np.float32
    sums = ew.sum(axis=1)
    np.testing.assert_allclose(sums[sums > 0], 1.0, atol=1e-6)
    want = np.zeros((n, n), np.float32)
    np.add.at(want, (np.repeat(np.arange(n), k), nb.reshape(-1)),
              ew.reshape(-1))
    np.testing.assert_array_equal(np.asarray(selection_matrix(g)), want)


@pytest.mark.parametrize("build", ("full", "delta", "jit", "empty"))
def test_sqmd_graphs_are_k_sparse(build):
    n, r, c, k = 9, 10, 3, 3
    labels = jax.random.randint(jax.random.key(3), (r,), 0, c)
    pol = as_policy(sqmd(q=5, k=k))
    st = init_server(n, r, c)
    if build != "empty":
        st = upload_messengers(st, _logp(n, r, c, seed=80),
                               jnp.ones(n, bool))
    if build == "jit":
        from repro.core import select_neighbors
        div = divergence_matrix(st.repo_logp)
        cand = jnp.arange(n) < 5
        g = jax.jit(lambda d, m: select_neighbors(d, m, k))(div, cand)
    else:
        mask = np.arange(n) < 2 if build == "delta" else None
        _, _, g = policy_round(st, pol, labels, uploaded=mask)
    _assert_k_sparse(g, n, k)
    if build == "empty":
        np.testing.assert_array_equal(np.asarray(g.edge_weights), 0.0)


def test_delta_and_full_rebuild_give_equal_k_sparse_targets():
    n, r, c = 11, 12, 3
    labels = jax.random.randint(jax.random.key(4), (r,), 0, c)
    pol = as_policy(sqmd(q=6, k=3))
    st = upload_messengers(init_server(n, r, c), _logp(n, r, c, seed=81),
                           jnp.ones(n, bool))
    st, _, _ = policy_round(st, pol, labels)
    mask = np.zeros(n, bool)
    mask[[1, 7]] = True
    st = upload_messengers(st, _logp(n, r, c, seed=82), jnp.asarray(mask))
    _, tgt_d, g_d = policy_round(st, pol, labels,
                                 uploaded=mask)
    _, tgt_f, g_f = policy_round(st, pol, labels)
    np.testing.assert_array_equal(np.asarray(g_d.neighbors),
                                  np.asarray(g_f.neighbors))
    np.testing.assert_array_equal(np.asarray(g_d.edge_weights),
                                  np.asarray(g_f.edge_weights))
    np.testing.assert_allclose(np.asarray(tgt_d), np.asarray(tgt_f),
                               atol=1e-6)
    # and both equal Eq. 5 over the dense selection matrix
    want = ref.neighbor_mean_ref(selection_matrix(g_f),
                                 jnp.exp(st.repo_logp))
    np.testing.assert_allclose(np.asarray(tgt_f), np.asarray(want),
                               atol=1e-6)


# --- ServerBus / engine integration ---------------------------------------

def _tiny_fed(n=5, r=8, c=3):
    from repro.core import Federation
    from repro.optim import sgd
    return Federation(cohorts=[], server=init_server(n, r, c),
                      protocol=sqmd(q=n, k=2),
                      ref_x=jnp.zeros((r, 4)),
                      ref_y=jnp.asarray(np.arange(r) % c),
                      optimizer=sgd(0.1), n_clients=n)


def test_server_bus_delta_keeps_cache_exact_across_fires():
    """delta=True: each fire consumes the accumulated fresh-uploader mask;
    the cache equals a from-scratch rebuild after every fire."""
    n = 5
    fed = _tiny_fed(n=n)
    from repro.core import EveryKUploads
    bus = ServerBus(fed, as_policy(sqmd(q=n, k=2)),
                    trigger=EveryKUploads(k=2), delta=True)
    rng = np.random.default_rng(7)
    for step in range(6):
        mask = rng.random(n) < 0.5
        msg = _logp(n, 8, 3, seed=60 + step)
        fired = bus.deliver(float(step), msg, mask)
        if fired:
            oracle = divergence_matrix(fed.server.repo_logp)
            np.testing.assert_allclose(np.asarray(fed.server.div_cache),
                                       np.asarray(oracle), atol=1e-5)
    assert bus.n_triggers >= 1


@pytest.mark.slow
def test_engine_delta_graph_end_to_end():
    """FederationConfig(delta_graph=True) trains under partial
    availability (staged joins => u < N uploads) with a cache that still
    matches the oracle at the end."""
    ds = pad_like(samples_per_client=16, ref_size=12, length=16)
    splits = make_splits(ds, seed=0)
    zoo = hetero_mlp_zoo(ds.feature_len, ds.n_classes)
    assignment = [list(zoo)[i % 3] for i in range(ds.n_clients)]
    n = ds.n_clients
    join = [0] * (n - 6) + [2] * 6
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=8, k=4),
        config=FederationConfig(rounds=3, batch_size=8, eval_every=2,
                                delta_graph=True),
        schedule=StagedJoin(join), seed=7)
    hist = engine.fit(splits)
    assert np.isfinite(hist.mean_acc).all()
    oracle = divergence_matrix(engine.server.repo_logp)
    np.testing.assert_allclose(np.asarray(engine.server.div_cache),
                               np.asarray(oracle), atol=1e-4)


def test_checkpoint_restores_legacy_server_without_div_cache(tmp_path):
    """Pre-delta checkpoints lack div_cache: restore rebuilds it from the
    repository so subsequent delta rounds stay exact."""
    from repro.checkpoint.io import restore_pytree, save_pytree
    from repro.checkpoint import restore_federation, save_federation
    fed = _tiny_fed()
    n, r, c = 5, 8, 3
    fed.server = upload_messengers(fed.server, _logp(n, r, c, seed=70),
                                   jnp.ones(n, bool))
    save_federation(str(tmp_path), fed, step=1)
    path = str(tmp_path / "step_1.msgpack")
    tree = restore_pytree(path)
    del tree["server"]["div_cache"]         # simulate a legacy checkpoint
    save_pytree(path, tree)
    fed2 = _tiny_fed()
    assert restore_federation(str(tmp_path), fed2) == 1
    np.testing.assert_allclose(
        np.asarray(fed2.server.div_cache),
        np.asarray(ref.pairwise_kl_ref(fed2.server.repo_logp)), atol=1e-6)


@pytest.mark.parametrize("legacy", ("weights", "sim"))
def test_checkpoint_restores_legacy_server_with_weights(tmp_path, legacy):
    """Checkpoints written while the selection matrix (``weights``) or the
    similarity C (``sim``) was server state carry that (N,N) key: restore
    drops it and the bus fires on."""
    from repro.checkpoint.io import restore_pytree, save_pytree
    from repro.checkpoint import restore_federation, save_federation
    n, r, c = 5, 8, 3
    fed = _tiny_fed()
    fed.server = upload_messengers(fed.server, _logp(n, r, c, seed=71),
                                   jnp.ones(n, bool))
    save_federation(str(tmp_path), fed, step=1)
    path = str(tmp_path / "step_1.msgpack")
    tree = restore_pytree(path)
    tree["server"][legacy] = np.full((n, n), 0.5, np.float32)
    save_pytree(path, tree)
    fed2 = _tiny_fed()
    assert restore_federation(str(tmp_path), fed2) == 1
    assert legacy not in fed2.server._fields
    bus = ServerBus(fed2, as_policy(sqmd(q=n, k=2)), delta=True)
    assert bus.deliver(1.0, _logp(n, r, c, seed=72), np.arange(n) < 2)
    _assert_k_sparse(bus.last_graph, n, 2)
    assert np.isfinite(np.asarray(fed2.targets)).all()


# --- frozen clients keep optimizer state bit-for-bit ----------------------

def test_frozen_client_matches_never_stepped_bit_for_bit():
    """A client frozen for 10 steps must be indistinguishable from one
    that never stepped: params AND every optimizer leaf (incl. the scalar
    Adam step counter driving bias correction) stay bit-identical."""
    from repro.core.client import cohort_step, make_cohort
    from repro.models.mlp import MLPConfig, apply_mlp, init_mlp
    from repro.optim import adam

    cfg = MLPConfig("t", 6, (8,), 3)
    apply_fn = lambda p, x: apply_mlp(cfg, p, x)  # noqa: E731
    opt = adam(0.05)
    coh = make_cohort("t", lambda k: init_mlp(k, cfg), apply_fn, opt,
                      [0, 1], {}, jax.random.key(0))
    p0 = jax.tree.map(lambda x: np.asarray(x).copy(), coh.params)
    s0 = jax.tree.map(lambda x: np.asarray(x).copy(), coh.opt_state)
    x = jax.random.normal(jax.random.key(1), (2, 4, 6))
    y = jax.random.randint(jax.random.key(2), (2, 4), 0, 3)
    ref_x = jax.random.normal(jax.random.key(3), (5, 6))
    tgt = jax.nn.softmax(jax.random.normal(jax.random.key(4), (2, 5, 3)), -1)
    params, opt_state = coh.params, coh.opt_state
    for _ in range(10):
        params, opt_state, _ = cohort_step(
            apply_fn, opt, params, opt_state, x, y, ref_x, tgt,
            jnp.asarray([False, True]), 0.5, True)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(b)[0])
    for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(opt_state)):
        np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(b)[0])
    # ... while the active client really trained (step counter advanced)
    assert int(np.asarray(opt_state.step)[1]) == 10
    assert int(np.asarray(opt_state.step)[0]) == 0


# --- ddist sparse-candidate edge cases ------------------------------------

def test_ddist_zero_active_clients_yields_zero_graph_no_nan():
    g = ddist_graph(jax.random.key(0), 6, 4, active=jnp.zeros(6, bool))
    w = np.asarray(selection_matrix(g))
    assert np.isfinite(w).all()
    np.testing.assert_allclose(w, 0.0)


def test_ddist_fewer_candidates_than_k_clamps_per_row():
    """With 2 active clients and k=4 each row realizes at most 1 non-self
    candidate — never an inactive neighbor, rows renormalized."""
    active = jnp.asarray([True, True, False, False, False, False])
    g = ddist_graph(jax.random.key(1), 6, 4, active=active)
    w = np.asarray(selection_matrix(g))
    assert np.isfinite(w).all()
    np.testing.assert_allclose(w[:, 2:], 0.0)       # inactive never sampled
    np.testing.assert_allclose(np.diag(w), 0.0)     # never self
    np.testing.assert_allclose(w[0], np.eye(6)[1])  # row 0 -> client 1
    np.testing.assert_allclose(w[1], np.eye(6)[0])  # row 1 -> client 0


def test_ddist_full_population_unchanged_properties():
    g = ddist_graph(jax.random.key(7), 10, 4)
    w = np.asarray(selection_matrix(g))
    assert np.allclose(np.diag(w), 0.0)
    np.testing.assert_allclose(w.sum(1), 1.0, atol=1e-6)
    assert ((w > 0).sum(1) == 4).all()
