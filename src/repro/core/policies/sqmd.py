"""SQMD — the paper's protocol: quality top-Q filter, then similarity
top-K neighbors on the dynamic directed graph (Defs. 3-5, Algorithm 1)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import graph as graph_mod
from repro.core import quality as quality_mod
from repro.core import similarity as sim_mod
from repro.core.policies.base import ServerPolicy, register_policy
from repro.obs import host_read


@register_policy("sqmd")
class SQMDPolicy(ServerPolicy):
    """Top-Q candidate pool by grade, top-K most-similar neighbors each."""

    def __init__(self, protocol=None):
        super().__init__(protocol)
        self._ivf = None  # lazily-built NeighborIndex (selection == "ivf")

    def build_graph(self, state, quality: jnp.ndarray):
        # self.mesh (bus-attached) shards the O(N²·R·C) rebuild row-wise
        # over the client mesh; None is the single-device oracle
        cand = self._pool(state, quality)
        div = sim_mod.divergence_matrix(state.repo_logp, mesh=self.mesh)
        return graph_mod.select_neighbors(div, cand, self.protocol.k)

    def build_graph_delta(self, state, quality: jnp.ndarray, uploaded):
        """O(u·N·R·C) round: scatter the uploaded rows' divergence strips
        into the cached matrix instead of rebuilding all N² pairs — or,
        under ``selection == "ivf"``, skip the (N,N) matrix entirely and
        maintain the approximate NeighborIndex at O(u·candidates)."""
        if self.selection == "ivf":
            return self._build_graph_ivf(state, quality, uploaded)
        cand = self._pool(state, quality)
        div = sim_mod.update_divergence_cache(state.div_cache,
                                              state.repo_logp, uploaded)
        return graph_mod.select_neighbors(div, cand, self.protocol.k)

    def _pool(self, state, quality: jnp.ndarray):
        """The Def. 3 pool as a host mask, read before the divergence work
        is queued: the device runs programs in order, so the read then
        waits for the grades alone and not for the strips and scatter,
        which run while the host goes on. Under a trace it stays a tracer
        (the graph then takes the dense fallback)."""
        cand = quality_mod.candidate_mask(quality, state.active,
                                          self.protocol.q)
        if isinstance(cand, jax.core.Tracer):
            return cand
        return host_read(cand, "select.pool", bool)

    # -- approximate (IVF) path -------------------------------------------
    def _index_for(self, state) -> sim_mod.NeighborIndex:
        n, r, c = state.repo_logp.shape
        if self._ivf is None or self._ivf.capacity != n:
            self._ivf = sim_mod.NeighborIndex(n, r, c, k=self.protocol.k)
        return self._ivf

    def _build_graph_ivf(self, state, quality: jnp.ndarray, uploaded):
        """Sub-quadratic round: keep per-client top-L neighbor lists in
        the IVF index and emit their K-sparse graph, (N,K) and (N,)
        arrays alone. ``graph.divergence`` stays None so the dense
        div_cache is never touched (nor trusted)."""
        idx = self._index_for(state)
        uploaded = np.asarray(uploaded)
        if uploaded.dtype != bool:
            raise TypeError(f"uploaded must be a boolean mask, got dtype "
                            f"{uploaded.dtype}")
        active = host_read(state.active, "ivf.active", bool)
        # first fire must also ingest rows that joined before the index
        # existed; re-uploads refresh their wire form + lists
        ingest = (uploaded | ~idx.active_rows()) & active
        rows = np.nonzero(ingest)[0]
        if rows.size:
            idx.update(rows, host_read(state.repo_logp, "ivf.repo")[rows])
        idx.sync_active(active)
        cand = host_read(quality_mod.candidate_mask(
            quality, state.active, self.protocol.q), "ivf.pool", bool)
        n = active.shape[0]
        k = max(1, min(self.protocol.k, n - 1))
        nbrs, _ = idx.select(cand, k)
        valid = nbrs >= 0
        count = valid.sum(axis=1)
        vals = np.where(valid, 1.0 / np.maximum(count, 1)[:, None], 0.0)
        return graph_mod.k_sparse(
            jnp.asarray(np.where(valid, nbrs, 0).astype(np.int32)),
            jnp.asarray(vals.astype(np.float32)), jnp.asarray(cand))
