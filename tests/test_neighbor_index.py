"""The IVF approximate neighbor index (core/similarity.NeighborIndex)
and the ``selection="ivf"`` policy/engine path.

The load-bearing contract: with ``n_probe >= n_centroids`` (probe-all)
the incrementally-maintained lists are EXACTLY the top-L over active
clients after ANY sequence of uploads / re-uploads / deactivations —
the hypothesis test drives arbitrary sequences against a dense oracle
computed off the same int8 wire form. Partial probing keeps the
structural invariants (no self / ghost / inactive / non-candidate ever
selected) but trades exactness for cost; that quality is measured by
benchmarks/ann_scale.py, not asserted here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import selection_matrix, wire
from repro.core.similarity import NeighborIndex
from repro.kernels import ops

R, C = 5, 7
PROBE_ALL = 10 ** 6


def _rand_logp(rng, u, r=R, c=C):
    x = rng.normal(size=(u, r, c)).astype(np.float32) * 2.0
    return np.array(jax.nn.log_softmax(jnp.asarray(x), axis=-1))


def _oracle_divergence(logp, n):
    """Dense (n,n) divergence off the SAME int8 round trip the index
    stores — the exact oracle the lists must reproduce."""
    codec = wire.get_codec("int8")()
    dec = codec.decode(codec.encode(jnp.asarray(logp), domain="log"))
    return np.asarray(ops.pairwise_kl_pair(dec, dec, backend="jnp"))


def _oracle_topk_div(div, i, ok_mask, k):
    ok = ok_mask.copy()
    ok[i] = False
    d = np.where(ok, div[i], np.inf)
    vals = np.sort(d, kind="stable")[:k]
    return vals[np.isfinite(vals)]


def _assert_matches_oracle(idx, logp, active, cand, k):
    div = _oracle_divergence(logp, active.size)
    nbrs, ndiv = idx.select(cand, k)
    for i in np.nonzero(active)[0]:
        got = ndiv[i][np.isfinite(ndiv[i])]
        want = _oracle_topk_div(div, i, active & cand, k)
        assert got.size == want.size, (i, got, want)
        np.testing.assert_allclose(got, want, atol=1e-5)
        for a in nbrs[i]:
            if a >= 0:
                assert active[a] and cand[a] and a != i


def test_probe_all_matches_oracle_after_uploads():
    rng = np.random.default_rng(0)
    n, k = 48, 4
    idx = NeighborIndex(n, R, C, k=k, n_probe=PROBE_ALL)
    logp = np.zeros((n, R, C), np.float32)
    active = np.zeros(n, bool)
    for _ in range(8):
        rows = rng.choice(n, size=rng.integers(1, 7), replace=False)
        lp = _rand_logp(rng, rows.size)
        logp[rows] = lp
        active[rows] = True
        idx.update(rows, lp)
    _assert_matches_oracle(idx, logp, active, active.copy(), k)


def test_reupload_changes_lists_exactly():
    """Re-uploading a row with a new messenger must propagate into every
    OTHER row's list (the reverse-merge + degraded-rebuild path)."""
    rng = np.random.default_rng(1)
    n, k = 24, 3
    idx = NeighborIndex(n, R, C, k=k, n_probe=PROBE_ALL)
    logp = _rand_logp(rng, n)
    active = np.ones(n, bool)
    idx.update(np.arange(n), logp)
    for _ in range(5):
        rows = rng.choice(n, size=3, replace=False)
        lp = _rand_logp(rng, 3)
        logp[rows] = lp
        idx.update(rows, lp)
    _assert_matches_oracle(idx, logp, active, active.copy(), k)


def test_deactivation_never_selected_and_lists_repair():
    rng = np.random.default_rng(2)
    n, k = 32, 4
    idx = NeighborIndex(n, R, C, k=k, n_probe=PROBE_ALL)
    logp = _rand_logp(rng, n)
    active = np.ones(n, bool)
    idx.update(np.arange(n), logp)
    drop = rng.choice(n, size=8, replace=False)
    active[drop] = False
    idx.sync_active(active)
    nbrs, _ = idx.select(active, k)
    assert not np.isin(nbrs[nbrs >= 0], drop).any()
    _assert_matches_oracle(idx, logp, active, active.copy(), k)


def test_candidate_mask_restricts_selection():
    rng = np.random.default_rng(3)
    n, k = 20, 3
    idx = NeighborIndex(n, R, C, k=k, n_probe=PROBE_ALL)
    idx.update(np.arange(n), _rand_logp(rng, n))
    cand = np.zeros(n, bool)
    cand[: n // 2] = True
    nbrs, _ = idx.select(cand, k)
    picked = nbrs[nbrs >= 0]
    assert picked.size > 0
    assert cand[picked].all()


def test_ghost_rows_never_selected():
    """Rows never ingested (no wire form) must not appear in any list."""
    rng = np.random.default_rng(4)
    n, k = 30, 4
    idx = NeighborIndex(n, R, C, k=k, n_probe=PROBE_ALL)
    real = np.arange(0, n, 2)          # odd rows are ghosts
    idx.update(real, _rand_logp(rng, real.size))
    nbrs, _ = idx.select(np.ones(n, bool), k)
    assert (nbrs[nbrs >= 0] % 2 == 0).all()


def test_partial_probe_structural_invariants():
    """With few probes the lists are approximate but must still never
    contain self / inactive / non-candidate entries."""
    rng = np.random.default_rng(5)
    n, k = 64, 4
    idx = NeighborIndex(n, R, C, k=k, n_probe=1)
    active = np.zeros(n, bool)
    for _ in range(6):
        rows = rng.choice(n, size=8, replace=False)
        active[rows] = True
        idx.update(rows, _rand_logp(rng, rows.size))
    drop = rng.choice(np.nonzero(active)[0], size=4, replace=False)
    active[drop] = False
    idx.sync_active(active)
    cand = active.copy()
    cand[np.nonzero(cand)[0][:3]] = False
    nbrs, _ = idx.select(cand, k)
    for i in range(n):
        for a in nbrs[i]:
            if a >= 0:
                assert a != i and active[a] and cand[a]


def test_update_dedups_unsorted_rows():
    """Duplicate/unsorted row ids must keep payload rows aligned (the
    last write for a duplicated id wins, like upload_messengers)."""
    rng = np.random.default_rng(6)
    n = 12
    idx = NeighborIndex(n, R, C, k=2, n_probe=PROBE_ALL)
    lp = _rand_logp(rng, 4)
    idx.update(np.array([7, 3, 7, 1]), lp)
    np.testing.assert_allclose(idx._recon_logp(np.array([3]))[0],
                               idx._recon_logp(np.array([3]))[0])
    # row 7 must hold the LAST payload row written for id 7 (index 2)
    codec_logp = np.asarray(wire.get_codec("int8")().decode(
        wire.get_codec("int8")().encode(jnp.asarray(lp[2:3]),
                                        domain="log")))[0]
    np.testing.assert_allclose(idx._recon_logp(np.array([7]))[0],
                               codec_logp, atol=1e-5)


def test_validation_errors():
    with pytest.raises(ValueError):
        NeighborIndex(0, R, C, k=2)
    with pytest.raises(ValueError):
        NeighborIndex(8, R, C, k=0)
    idx = NeighborIndex(8, R, C, k=2)
    with pytest.raises(ValueError):
        idx.update(np.array([8]), _rand_logp(np.random.default_rng(0), 1))
    with pytest.raises(ValueError):
        idx.select(np.ones(5, bool))
    with pytest.raises(ValueError):
        idx.sync_active(np.ones(5, bool))


def test_config_rejects_ivf_without_delta():
    from repro.core.engine import FederationConfig
    with pytest.raises(ValueError):
        FederationConfig(selection="ivf")
    with pytest.raises(ValueError):
        FederationConfig(selection="bogus")
    cfg = FederationConfig(selection="ivf", delta_graph=True)
    assert cfg.selection == "ivf"


def test_policy_ivf_graph_shape_and_edges():
    """The SQMD ivf branch emits a well-formed CollaborationGraph: row-
    stochastic weights on realized edges, candidates respected, (N,K) and
    (N,) arrays alone, dense div_cache untouched — and the server state
    holds no (N,N) array but that div_cache."""
    from repro.core import init_server, upload_messengers
    from repro.core.policies import as_policy

    rng = np.random.default_rng(7)
    n, r, c = 24, R, C
    logp = jnp.asarray(_rand_logp(rng, n, r, c))
    state = upload_messengers(init_server(n, r, c), logp,
                              jnp.ones((n,), bool))
    pol = as_policy("sqmd")
    pol.selection = "ivf"
    pol._ivf = NeighborIndex(n, r, c, k=pol.protocol.k,
                             n_probe=PROBE_ALL)
    quality = pol.grade(state, jnp.zeros((r,), jnp.int32))
    uploaded = np.ones(n, bool)
    g = pol.build_graph_delta(state, quality, uploaded)
    k = pol.protocol.k
    shapes = {f: np.shape(v) for f, v in g._asdict().items()
              if v is not None}
    assert shapes == {"neighbors": (n, k), "edge_weights": (n, k),
                      "candidates": (n,)}
    state2 = pol.update_state(state, quality, g)
    assert state2.div_cache is state.div_cache
    assert [f for f in state2._fields
            if np.shape(getattr(state2, f)) == (n, n)] == ["div_cache"]
    w = np.asarray(selection_matrix(g))
    assert w.shape == (n, n)
    sums = w.sum(axis=1)
    np.testing.assert_allclose(sums[sums > 0], 1.0, atol=1e-5)
    assert g.divergence is None
    assert np.diag(w).max() == 0.0
    cand = np.asarray(g.candidates)
    assert (w[:, ~cand] == 0).all()
    with pytest.raises(TypeError):
        pol.build_graph_delta(state, quality, uploaded.astype(np.int32))


def test_engine_ivf_end_to_end_matches_exact_graph_edges():
    """A tiny federation run with selection='ivf' under probe-all picks
    the same neighbor EDGES as the exact dense path each fire."""
    from repro.core import init_server, upload_messengers
    from repro.core.policies import as_policy
    from repro.core.protocols import sqmd as sqmd_proto

    rng = np.random.default_rng(8)
    n, r, c, k = 20, R, C, 3
    logp = jnp.asarray(_rand_logp(rng, n, r, c))
    state = upload_messengers(init_server(n, r, c), logp,
                              jnp.ones((n,), bool))
    proto = sqmd_proto(q=12, k=k)

    exact = as_policy(proto)
    ivf = as_policy(proto)
    ivf.selection = "ivf"
    ivf._ivf = NeighborIndex(n, r, c, k=k, n_probe=PROBE_ALL)
    labels = jnp.zeros((r,), jnp.int32)
    quality = exact.grade(state, labels)
    uploaded = np.ones(n, bool)

    g_exact = exact.build_graph(state, quality)
    g_ivf = ivf.build_graph_delta(state, quality, uploaded)
    # compare edge sets per row; int8 round-trip shifts divergences a
    # little, so compare against the oracle computed off the wire form
    div = _oracle_divergence(np.asarray(logp), n)
    cand = np.asarray(g_ivf.candidates)
    w_ivf = np.asarray(selection_matrix(g_ivf))
    for i in range(n):
        got = set(np.nonzero(w_ivf[i])[0])
        want = set(np.argsort(np.where(
            cand & (np.arange(n) != i), div[i], np.inf),
            kind="stable")[:k])
        assert got == want, (i, got, want)
    # and the exact path agrees on shape/candidates
    assert np.asarray(g_exact.candidates).sum() == cand.sum()


# -- hypothesis property tests ---------------------------------------------
# optional dep: guard only these tests, NOT the whole module (the unit
# tests above must run even where hypothesis is absent)
try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:

    @settings(max_examples=12, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 6))
    def test_property_probe_all_exact_over_arbitrary_sequences(seed,
                                                               steps):
        """Probe-all lists == exact oracle top-k after ANY upload /
        re-upload / deactivation sequence; no ghost or inactive client
        is ever selected."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(6, 40))
        k = int(rng.integers(1, 5))
        idx = NeighborIndex(n, R, C, k=k, n_probe=PROBE_ALL)
        logp = np.zeros((n, R, C), np.float32)
        active = np.zeros(n, bool)
        for _ in range(steps):
            u = int(rng.integers(1, max(2, n // 3)))
            rows = rng.choice(n, size=u, replace=False)
            lp = _rand_logp(rng, u)
            logp[rows] = lp
            active[rows] = True
            idx.update(rows, lp)
            if rng.random() < 0.4 and active.sum() > 2:
                drop = rng.choice(np.nonzero(active)[0], size=1)
                active[drop] = False
                idx.sync_active(active)
        if active.sum() == 0:
            return
        _assert_matches_oracle(idx, logp, active, active.copy(), k)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_property_partial_probe_never_ghosts(seed):
        """Under arbitrary partial probing the lists stay structurally
        sound: only active, ingested, non-self ids are ever selected."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(8, 48))
        probe = int(rng.integers(1, 4))
        idx = NeighborIndex(n, R, C, k=3, n_probe=probe)
        active = np.zeros(n, bool)
        for _ in range(4):
            u = int(rng.integers(1, max(2, n // 4)))
            rows = rng.choice(n, size=u, replace=False)
            active[rows] = True
            idx.update(rows, _rand_logp(rng, u))
        nbrs, ndiv = idx.select(np.ones(n, bool), 3)
        for i in range(n):
            for a, d in zip(nbrs[i], ndiv[i]):
                if a >= 0:
                    assert active[a] and a != i and np.isfinite(d)
                else:
                    assert not np.isfinite(d)
