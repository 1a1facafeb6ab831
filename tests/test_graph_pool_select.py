"""The pool-column selection against the dense formula it replaces.

SQMD's server selects each client's K neighbours from the Q pool columns
of the divergence matrix. The oracle is the whole-matrix form:
``similarity_matrix(div)`` (Def. 4, zero diagonal), then the top-k over
its pool columns (``_oracle``; ``_dense_select`` adds the pool from a
candidate mask, as ``select_neighbors`` takes it).
The two must agree bit for bit, on neighbours and on edge weights,
including ties, pools smaller than k, padded pool slots and divergences
at or under the EPS floor.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (init_server, policy_round, similarity_matrix, sqmd,
                        upload_messengers)
from repro.core import graph
from repro.core.policies import as_policy
from repro.core.quality import BIG
from repro.core.similarity import EPS

K = 8


def _oracle(div, pool, valid, k):
    """The whole-matrix formula, written out: Def. 4 over all of ``div``
    (zero diagonal), its pool columns, padded and self slots at -BIG,
    top-k, 1/count on the realized edges."""
    sim = similarity_matrix(div)
    rows = jnp.arange(div.shape[0], dtype=pool.dtype)[:, None]
    sub = jnp.where(valid[None, :] & (pool[None, :] != rows),
                    sim[:, pool], -BIG)
    vals, at = jax.lax.top_k(sub, k)
    real = (vals > -BIG / 2).astype(jnp.float32)
    return (pool[at].astype(jnp.int32),
            real / jnp.maximum(real.sum(axis=1, keepdims=True), 1.0))


def _dense_select(div, cand: np.ndarray, k: int):
    """``_oracle`` from a candidate mask: the pool padded to its bucket,
    and an empty pool's K unrealized slots of weight 0."""
    n = div.shape[0]
    k = min(k, n - 1)
    bucket = graph._pool_bucket(np.asarray(cand, bool), k)
    if bucket is None:
        return np.zeros((n, k), np.int32), np.zeros((n, k), np.float32)
    return _oracle(div, *(jnp.asarray(a) for a in bucket), k)


def _div(n: int, values: str, seed: int) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    if values == "ties":
        # three distinct values: nearly every row's top-k is decided by ties
        d = rng.integers(1, 4, (n, n)).astype(np.float32)
    else:
        d = rng.exponential(1.0, (n, n)).astype(np.float32)
    if values == "zero_eps":
        # exact zeros and values at or under the floor all score 1/EPS
        pick = rng.random((n, n))
        d[pick < 0.2] = 0.0
        d[(pick >= 0.2) & (pick < 0.3)] = EPS
        d[(pick >= 0.3) & (pick < 0.35)] = EPS / 4
    np.fill_diagonal(d, 0.0)
    return jnp.asarray(d)


def _candidates(n: int, pool: str, seed: int) -> np.ndarray:
    """``under_k``: fewer candidates than k; ``padded``: a pool size that
    is no power of two, so the bucket has padded slots; ``all``: every
    client, so each row's own index lies in the pool."""
    size = {"under_k": min(3, n - 1), "padded": max(2, (n * 3) // 5),
            "all": n}[pool]
    rng = np.random.default_rng(seed + 1)
    cand = np.zeros(n, bool)
    cand[rng.choice(n, size, replace=False)] = True
    return cand


@pytest.mark.parametrize("values", ["spread", "zero_eps", "ties"])
@pytest.mark.parametrize("pool", ["under_k", "padded", "all"])
@pytest.mark.parametrize("n", [5, 32, 257])
def test_pool_selection_matches_dense_formula(n, pool, values):
    div = _div(n, values, seed=n)
    k = min(K, n - 1)
    bucket = graph._pool_bucket(_candidates(n, pool, seed=n), k)
    pool_ix, valid = (jnp.asarray(a) for a in bucket)
    want = _oracle(div, pool_ix, valid, k)
    got = graph._select_pool_div(div, pool_ix, valid, k)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.asarray(got[0]).shape == (n, k)


@pytest.mark.parametrize("values", ["spread", "zero_eps"])
@pytest.mark.parametrize("n", [5, 32, 257])
def test_pool_columns_read_div_columns_bit_for_bit(n, values):
    """The one-hot gather gives ``div[:, pool]`` back bit for bit, with
    repeated ids and wide exponents; -0.0 comes back as a zero."""
    div = _div(n, values, seed=3).at[0, n - 1].set(-0.0)
    div = div.at[1, 0].set(3.0e38).at[2, 1].set(1.0e-37)
    pool_ix = jnp.asarray(np.array([n - 1, 0, n // 2, 0, 1, n - 1],
                                   np.int32))
    got = np.asarray(graph._pool_columns(div, pool_ix))
    want = np.asarray(jnp.take(div, pool_ix, axis=1))
    np.testing.assert_array_equal(got, want)
    nonzero = want != 0
    np.testing.assert_array_equal(got.view(np.uint32)[nonzero],
                                  want.view(np.uint32)[nonzero])


@pytest.mark.parametrize("values", ["spread", "ties"])
@pytest.mark.parametrize("pool", ["under_k", "padded", "all", "empty"])
def test_sqmd_graph_carries_no_similarity(pool, values):
    n = 32
    div = _div(n, values, seed=7)
    cand = (np.zeros(n, bool) if pool == "empty"
            else _candidates(n, pool, seed=7))
    g = graph.select_neighbors(div, jnp.asarray(cand), K)
    want = _dense_select(div, cand, K)
    assert "similarity" not in graph.CollaborationGraph._fields
    assert g.divergence is div
    np.testing.assert_array_equal(np.asarray(g.neighbors),
                                  np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(g.edge_weights),
                                  np.asarray(want[1]))


def _logp(n, r, c, seed):
    z = jax.random.normal(jax.random.key(seed), (n, r, c)) * 2.0
    return jax.nn.log_softmax(z, -1)


@pytest.mark.parametrize("path", ["full", "delta"])
def test_policy_round_selects_as_dense_formula(path):
    """Through ``policy_round``: a full rebuild, then (for ``delta``) a
    delta update of 3 uploaded rows. Each graph's neighbours and edge
    weights are the dense formula's over its own divergence, and the
    state's one (N,N) array, ``div_cache``, is the graph's."""
    n, r, c, q = 32, 12, 3, 12
    labels = jax.random.randint(jax.random.key(0), (r,), 0, c)
    policy = as_policy(sqmd(q=q, k=K))
    st = init_server(n, r, c)
    st = upload_messengers(st, _logp(n, r, c, seed=1), jnp.ones(n, bool))
    st, _, g = policy_round(st, policy, labels)
    if path == "delta":
        up = np.zeros(n, bool)
        up[[2, 9, 30]] = True
        st = upload_messengers(st, _logp(n, r, c, seed=2), jnp.asarray(up))
        st, _, g = policy_round(st, policy, labels, uploaded=up)
    want = _dense_select(g.divergence, g.candidates, K)
    np.testing.assert_array_equal(np.asarray(g.neighbors),
                                  np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(g.edge_weights),
                                  np.asarray(want[1]))
    assert st.div_cache is g.divergence
    assert [f for f in st._fields
            if getattr(st, f).shape == (n, n)] == ["div_cache"]
