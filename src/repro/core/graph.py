"""The dynamic directed collaboration graph (paper Def. 5).

G = (A, E, C): nodes are clients, the fp32 weight matrix C holds c_nm, and
each round the server re-derives every client's neighbor set K^n — the K
most-similar members of the quality pool Q (excluding the client itself).
SQMD's graph is carried K-sparse: ``neighbors`` (N,K) and ``edge_weights``
(N,K), 1/count on each realized edge and 0 on unrealized slots, which is
all Eq. 5 reads (``ops.neighbor_mean``). Graphs that are dense by nature
(FedMD, D-Dist) carry the row-stochastic (N,N) ``weights`` instead;
``selection_matrix`` gives either as the dense W off the hot path. No
graph carries the (N,N) similarity C: SQMD's selection computes Def. 4 on
the Q pool columns of the divergence alone, and ``similarity_matrix``
(``core/similarity.py``) gives the whole C of a ``divergence`` off the
hot path.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quality import BIG
from repro.obs import host_read, span


class CollaborationGraph(NamedTuple):
    neighbors: jnp.ndarray       # (N, K) int32 neighbor indices
    weights: Optional[jnp.ndarray]  # (N, N) fp32 row-stochastic selection
    # matrix of a dense graph; None on K-sparse graphs
    candidates: jnp.ndarray      # (N,) bool — the Q pool
    divergence: Optional[jnp.ndarray] = None  # (N,N) fp32 Eq.2 matrix this
    # graph was built from; policies that compute it surface it here so
    # update_state can persist it as ServerState.div_cache (delta path)
    edge_weights: Optional[jnp.ndarray] = None  # (N, K) fp32 weight of
    # each neighbor slot (0 on unrealized ones): set on K-sparse graphs


def k_sparse(neighbors, edge_weights, candidates,
             divergence=None) -> CollaborationGraph:
    """A graph carried as its (N,K) neighbors and edge weights alone."""
    return CollaborationGraph(neighbors=neighbors, weights=None,
                              candidates=candidates, divergence=divergence,
                              edge_weights=edge_weights)


@functools.partial(jax.jit, static_argnames=("n",))
def _scatter_edges(neighbors, edge_weights, n: int):
    k = neighbors.shape[1]
    rows = jnp.repeat(jnp.arange(n), k)
    return jnp.zeros((n, n), jnp.float32).at[
        rows, neighbors.reshape(-1)].add(
            edge_weights.astype(jnp.float32).reshape(-1))


def selection_matrix(g: CollaborationGraph) -> jnp.ndarray:
    """The dense (N,N) row-stochastic W of any graph, for readers off the
    hot path (stats, tests): ``weights`` where the graph carries it, else
    scattered from its neighbors and edge weights."""
    if g.weights is not None:
        return g.weights
    return _scatter_edges(g.neighbors, g.edge_weights,
                          g.neighbors.shape[0])


def _pool_columns(div: jnp.ndarray, pool: jnp.ndarray) -> jnp.ndarray:
    """The (N,B) pool columns ``div[:, pool]``, as ``div`` times the (B,N)
    one-hot of ``pool`` at HIGHEST precision. The matmul reads ``div`` in
    its stored row-major layout (a column ``take`` transposes the whole
    matrix on the TPU first) and keeps a row split over a client mesh
    with no collective. Each column is one term x·1 among zeros, which
    HIGHEST's float32-exact passes give back bit for bit for finite x (a
    zero may lose its sign, which Def. 4's EPS floor does not see); Eq. 2
    of finite log-probabilities is finite."""
    onehot = jax.nn.one_hot(pool, div.shape[1], dtype=div.dtype)
    return jax.lax.dot_general(div, onehot, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("k",))
def _select_pool_div(div: jnp.ndarray, pool: jnp.ndarray,
                     pool_valid: jnp.ndarray, k: int):
    """Def. 4 + the pool top-k from the (N,N) divergence matrix, on its B
    pool columns alone: O(N·B·log k) instead of O(N²·log k), and no
    (N,N) value is written, copied or transposed. The scores are
    ``similarity_matrix(div)[:, pool]`` bit for bit (the same elementwise
    ops; the pool mask covers the zeroed diagonal), in pool order, so
    ``top_k`` breaks ties as over the whole matrix."""
    from repro.core.similarity import EPS
    sub = 1.0 / jnp.maximum(_pool_columns(div, pool), EPS)
    # padded slots and self-edges are unrealizable
    rowidx = jnp.arange(sub.shape[0], dtype=pool.dtype)[:, None]
    sub = jnp.where(pool_valid[None, :] & (pool[None, :] != rowidx),
                    sub, -BIG)
    return _topk_weights(sub, pool, k)


def _topk_weights(sub: jnp.ndarray, pool: jnp.ndarray, k: int):
    """(N,B) masked pool scores -> ((N,K) neighbors, (N,K) edge weights):
    1/count on each row's realized edges, 0 on the rest."""
    top_vals, top_sub = jax.lax.top_k(sub, k)               # (N, K)
    nbrs = pool[top_sub].astype(jnp.int32)
    valid = top_vals > -BIG / 2                             # realized edges
    count = jnp.sum(valid.astype(jnp.float32), axis=1, keepdims=True)
    return nbrs, valid.astype(jnp.float32) / jnp.maximum(count, 1.0)


def _empty(n: int, k: int):
    """No candidates: K unrealized slots per row, all of weight 0."""
    return jnp.zeros((n, k), jnp.int32), jnp.zeros((n, k), jnp.float32)


def _pool_bucket(candidates, k: int):
    """Candidate mask (a host array, or a device array read here) -> host
    (padded pool indices, validity) or None if the pool is empty.
    Power-of-two padding keeps jit compiles per-bucket."""
    if not isinstance(candidates, np.ndarray):
        candidates = host_read(candidates, "select.pool", bool)
    pool = np.nonzero(candidates)[0]
    if pool.size == 0 or k == 0:
        return None
    bucket = max(1 << (pool.size - 1).bit_length(), k)
    return (np.pad(pool.astype(np.int32), (0, bucket - pool.size)),
            np.arange(bucket) < pool.size)


def _select_dense(similarity: jnp.ndarray, candidates: jnp.ndarray, k: int):
    """Jit-traceable fallback: top-k over all N columns with non-candidates
    masked to -BIG (the pre-pool algorithm; O(N²) but tracer-safe)."""
    n = similarity.shape[0]
    scores = jnp.where(candidates[None, :], similarity, -BIG)
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    scores = jnp.where(i == j, -2 * BIG, scores)
    return _topk_weights(scores, jnp.arange(n, dtype=jnp.int32), k)


def select_neighbors(divergence: jnp.ndarray, candidates,
                     k: int) -> CollaborationGraph:
    """Top-K most-similar candidates per client (directed edges n -> m),
    by the Def. 4 similarity of the (N,N) Eq. 2 ``divergence`` matrix.

    Clients outside Q still get K neighbors (paper: 'any client, regardless
    of its quality, is assigned K neighbors'). A client never selects
    itself. If fewer than K candidates exist, the row's edge weights are
    renormalized over the realized edges (unrealized slots weigh 0).

    Only the Q candidate columns are ever eligible, so Def. 4 and the
    top-k run over the (N, Q) pool columns, not all N² scores. The pool
    index set is padded to a power-of-two bucket (padded slots scored
    -BIG) so the jitted selection compiles once per bucket, not once per
    pool size. ``candidates`` may be a host mask, read before the
    divergence was queued (a device mask is read here). The pool
    extraction needs concrete values; under an outer jit trace the dense
    O(N²) path keeps the function traceable. The graph carries
    ``divergence``."""
    n = divergence.shape[0]
    k = min(k, n - 1)
    if isinstance(candidates, jax.core.Tracer):
        from repro.core.similarity import similarity_matrix
        sim = similarity_matrix(divergence)
        return k_sparse(*_select_dense(sim, candidates, k), candidates,
                        divergence)
    bucket = _pool_bucket(candidates, k)
    if bucket is None:
        return k_sparse(*_empty(n, k), candidates, divergence)
    pool, valid = bucket
    with span("repro.select", pool=int(valid.sum()), bucket=valid.size):
        nbrs, vals = _select_pool_div(divergence, jnp.asarray(pool),
                                      jnp.asarray(valid), k)
    return k_sparse(nbrs, vals, candidates, divergence)


def fedmd_graph(active: jnp.ndarray) -> CollaborationGraph:
    """FedMD baseline: everyone averages everyone (Q = K = N), i.e. a
    complete graph over active clients with uniform weights."""
    n = active.shape[0]
    a = active.astype(jnp.float32)
    w = jnp.tile(a[None, :], (n, 1))
    w = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1.0)
    nbrs = jnp.tile(jnp.arange(n, dtype=jnp.int32)[None, :], (n, 1))
    return CollaborationGraph(neighbors=nbrs, weights=w, candidates=active)


def ddist_graph(key, n: int, k: int, active: Optional[jnp.ndarray] = None
                ) -> CollaborationGraph:
    """D-Dist baseline: a STATIC random K-neighbor graph drawn once at
    setup (Bistritz et al. 2020); no server-side filtering.

    k is clamped per-row to the realized candidate count (active,
    non-self): a sparse federation never samples inactive neighbors, and a
    federation with zero active clients yields an all-zero (NaN-free)
    selection matrix. Rows renormalize over the realized edges, exactly
    like ``select_neighbors``."""
    if active is None:
        active = jnp.ones((n,), bool)
    k = min(k, n - 1)

    # Gumbel top-k == uniform sampling without replacement over the
    # positive-probability candidates; -inf scores mark unrealizable slots.
    def row(key_i, i):
        p = jnp.where(jnp.arange(n) == i, 0.0, active.astype(jnp.float32))
        scores = jax.random.gumbel(key_i, (n,)) + jnp.log(p)
        vals, idx = jax.lax.top_k(scores, k)
        return idx, jnp.isfinite(vals)

    keys = jax.random.split(key, n)
    nbrs, valid = jax.vmap(row)(keys, jnp.arange(n))
    nbrs = nbrs.astype(jnp.int32)
    w = jnp.zeros((n, n), jnp.float32)
    rows = jnp.repeat(jnp.arange(n), k)
    w = w.at[rows, nbrs.reshape(-1)].add(valid.reshape(-1).astype(jnp.float32))
    w = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1.0)
    return CollaborationGraph(neighbors=nbrs, weights=w, candidates=active)


def graph_stats(g: CollaborationGraph) -> dict:
    """Diagnostics for EXPERIMENTS.md: degree distribution, reciprocity."""
    adj = selection_matrix(g) > 0
    in_deg = adj.sum(axis=0)
    recip = jnp.logical_and(adj, adj.T).sum() / jnp.maximum(adj.sum(), 1)
    return {
        "out_degree": float(adj.sum(axis=1).mean()),
        "in_degree_max": int(in_deg.max()),
        "in_degree_min": int(in_deg.min()),
        "reciprocity": float(recip),
        "n_candidates": int(g.candidates.sum()),
    }
