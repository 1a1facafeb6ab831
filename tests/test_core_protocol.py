"""Unit tests for the SQMD protocol mechanics (quality, graph, server)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ServerState, as_policy, candidate_mask, ddist_graph,
                        fedmd_graph, init_server, policy_round,
                        quality_scores, select_neighbors, selection_matrix,
                        server_round, similarity_matrix, divergence_matrix,
                        upload_messengers)
from repro.core.protocols import ddist, fedmd, isgd, sqmd


def _logp(n, r, c, seed=0, sharp=2.0):
    z = jax.random.normal(jax.random.key(seed), (n, r, c)) * sharp
    return jax.nn.log_softmax(z, -1)


# --- quality / candidates -------------------------------------------------

def test_candidate_mask_selects_lowest_loss_active():
    q = jnp.asarray([5.0, 1.0, 3.0, 0.5, 9.0, 2.0])
    active = jnp.asarray([True, True, True, True, True, False])
    m = np.asarray(candidate_mask(q, active, 3))
    assert m.sum() == 3
    assert m[3] and m[1] and m[5] == False  # noqa: E712
    assert m[4] == False  # noqa: E712  (worst active excluded)


def test_candidate_mask_fewer_active_than_q():
    q = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    active = jnp.asarray([True, False, False, True])
    m = np.asarray(candidate_mask(q, active, 3))
    assert m.sum() == 2 and m[0] and m[3]


def test_candidate_mask_single_active_and_q_exceeding_n():
    q = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    one = jnp.asarray([False, False, True, False])
    m = np.asarray(candidate_mask(q, one, 3))
    assert m.tolist() == [False, False, True, False]
    # q > N clamps to the population without erroring
    m_big = np.asarray(candidate_mask(q, jnp.ones(4, bool), 99))
    assert m_big.all()


def test_candidate_mask_all_inactive_is_all_false():
    """Degenerate pool: zero active clients (e.g. an eval before anyone
    joined) must yield an all-False mask — the BIG sentinel scores of
    inactive rows never leak through top_k into the pool."""
    q = jnp.asarray([5.0, 1.0, 3.0, 0.5])
    m = np.asarray(candidate_mask(q, jnp.zeros(4, bool), 2))
    assert not m.any()


def test_server_round_all_inactive_no_nan_downstream():
    """A full SQMD server round over an all-inactive federation: the empty
    candidate pool must produce a zero graph and finite (zero) targets —
    no NaN reaches the clients."""
    n, r, c = 5, 10, 3
    labels = jax.random.randint(jax.random.key(0), (r,), 0, c)
    st = init_server(n, r, c)          # nobody has joined: active all-False
    st2, targets, g = policy_round(st, as_policy(sqmd(q=3, k=2)), labels,
                                   backend="jnp")
    assert np.isfinite(np.asarray(targets)).all()
    np.testing.assert_allclose(np.asarray(targets), 0.0)
    np.testing.assert_allclose(np.asarray(selection_matrix(g)), 0.0)
    assert np.isfinite(np.asarray(st2.sim)).all()


def test_server_state_holds_no_selection_matrix():
    """The graph is rebuilt every fire and kept by its caller: the state
    holds the two (N,N) arrays Eq. 2 and Def. 4 need, and no W."""
    assert "weights" not in ServerState._fields
    st = init_server(6, 4, 3)
    square = [f for f in ServerState._fields
              if getattr(st, f).shape == (6, 6)]
    assert sorted(square) == ["div_cache", "sim"]


def test_quality_ranks_better_model_lower():
    r, c = 30, 4
    labels = jax.random.randint(jax.random.key(1), (r,), 0, c)
    good = jax.nn.log_softmax(4.0 * jax.nn.one_hot(labels, c), -1)[None]
    rand = _logp(1, r, c, seed=2)
    g = np.asarray(quality_scores(jnp.concatenate([good, rand]), labels))
    assert g[0] < g[1]


# --- similarity / graph ---------------------------------------------------

def test_similarity_recovers_planted_clusters():
    """Two groups of clients with messengers perturbed around two anchors:
    top-K neighbors should be within-group."""
    r, c, per = 40, 5, 5
    a = _logp(1, r, c, seed=3, sharp=3.0)
    b = _logp(1, r, c, seed=4, sharp=3.0)
    reps = []
    for i in range(per):
        reps.append(jax.nn.log_softmax(a[0] * 1.0 + 0.05 *
                                       jax.random.normal(jax.random.key(10 + i), (r, c)), -1))
    for i in range(per):
        reps.append(jax.nn.log_softmax(b[0] * 1.0 + 0.05 *
                                       jax.random.normal(jax.random.key(20 + i), (r, c)), -1))
    logp = jnp.stack(reps)
    sim = similarity_matrix(divergence_matrix(logp, backend="jnp"))
    g = select_neighbors(sim, jnp.ones((2 * per,), bool), k=3)
    nbrs = np.asarray(g.neighbors)
    for i in range(2 * per):
        group = i // per
        assert all(n // per == group for n in nbrs[i]), (i, nbrs[i])


def test_select_neighbors_never_self_and_row_stochastic():
    logp = _logp(9, 20, 3, seed=5)
    sim = similarity_matrix(divergence_matrix(logp, backend="jnp"))
    g = select_neighbors(sim, jnp.ones((9,), bool), k=4)
    w = np.asarray(selection_matrix(g))
    assert np.allclose(np.diag(w), 0.0)
    np.testing.assert_allclose(w.sum(1), 1.0, atol=1e-6)
    assert ((w > 0).sum(1) == 4).all()


def test_select_neighbors_respects_candidates():
    logp = _logp(8, 20, 3, seed=6)
    sim = similarity_matrix(divergence_matrix(logp, backend="jnp"))
    cand = jnp.asarray([True, True, True, False, False, False, False, True])
    g = select_neighbors(sim, cand, k=3)
    w = np.asarray(selection_matrix(g))
    # only candidate columns may carry weight
    assert np.allclose(w[:, ~np.asarray(cand)], 0.0)
    # every client (incl. non-candidates) still gets neighbors
    assert (w.sum(1) > 0.99).all()


def test_fedmd_is_complete_graph_average():
    active = jnp.asarray([True, True, True, False])
    g = fedmd_graph(active)
    w = np.asarray(selection_matrix(g))
    np.testing.assert_allclose(w[:, :3], 1.0 / 3, atol=1e-6)
    np.testing.assert_allclose(w[:, 3], 0.0)


def test_ddist_static_graph_properties():
    g = ddist_graph(jax.random.key(7), 10, 4)
    w = np.asarray(selection_matrix(g))
    assert np.allclose(np.diag(w), 0.0)
    np.testing.assert_allclose(w.sum(1), 1.0, atol=1e-6)


# --- server round ---------------------------------------------------------

@pytest.mark.parametrize("proto", [sqmd(q=4, k=2), fedmd(), isgd()])
def test_server_round_targets_shape_and_validity(proto):
    n, r, c = 6, 15, 3
    labels = jax.random.randint(jax.random.key(8), (r,), 0, c)
    st = init_server(n, r, c)
    st = upload_messengers(st, _logp(n, r, c, seed=9), jnp.ones((n,), bool))
    st2, targets = server_round(st, proto, labels, backend="jnp")
    assert targets.shape == (n, r, c)
    assert int(st2.round) == 1
    if proto.name != "isgd":
        np.testing.assert_allclose(np.asarray(targets).sum(-1), 1.0,
                                   atol=1e-4)
    else:
        np.testing.assert_allclose(np.asarray(targets), 0.0)


def test_async_newcomer_excluded_from_candidates_but_served():
    """A newcomer with a bad (uniform) messenger must not be selected as a
    neighbor by converged clients, yet still receives K neighbors."""
    n, r, c = 6, 20, 4
    labels = jax.random.randint(jax.random.key(10), (r,), 0, c)
    good = jax.nn.log_softmax(
        3.0 * jax.nn.one_hot(labels, c)[None]
        + 0.3 * jax.random.normal(jax.random.key(11), (n - 1, r, c)), -1)
    newbie = jnp.full((1, r, c), -jnp.log(c))
    logp = jnp.concatenate([good, newbie])
    st = init_server(n, r, c)
    st = upload_messengers(st, logp, jnp.ones((n,), bool))
    st2, targets, g = policy_round(st, as_policy(sqmd(q=4, k=2)), labels,
                                   backend="jnp")
    w = np.asarray(selection_matrix(g))
    assert np.allclose(w[:, -1], 0.0), "newcomer poisoned the graph"
    assert w[-1].sum() > 0.99, "newcomer did not receive neighbors"


def test_stale_repository_rows_persist():
    n, r, c = 4, 10, 3
    st = init_server(n, r, c)
    m1 = _logp(n, r, c, seed=12)
    st = upload_messengers(st, m1, jnp.asarray([True, True, False, False]))
    np.testing.assert_allclose(np.asarray(st.repo_logp[0]),
                               np.asarray(m1[0]))
    # rows 2,3 still uniform
    np.testing.assert_allclose(np.asarray(st.repo_logp[2]),
                               -np.log(c), atol=1e-6)
    assert np.asarray(st.active).tolist() == [True, True, False, False]
