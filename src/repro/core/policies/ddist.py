"""D-Dist baseline (Bistritz et al. 2020): a static random K-neighbor
graph drawn once at setup; no server-side quality/similarity filtering."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core import graph as graph_mod
from repro.core.policies.base import ServerPolicy, register_policy


@register_policy("ddist")
class DDistPolicy(ServerPolicy):
    """Static graph, re-masked each round so never-joined clients carry no
    weight (their rows renormalize over the realized edges)."""

    def __init__(self, protocol=None,
                 static_weights: Optional[jnp.ndarray] = None):
        super().__init__(protocol)
        self.static_weights = static_weights

    def setup(self, key, n_clients: int) -> None:
        if self.static_weights is None:
            self.static_weights = graph_mod.ddist_graph(
                key, n_clients, self.protocol.k).weights

    def attach_static_weights(self, weights: jnp.ndarray) -> None:
        self.static_weights = weights

    def build_graph(self, state, quality: jnp.ndarray):
        if self.static_weights is None:
            raise ValueError("ddist needs its static graph: call "
                             "policy.setup(key, n) or pass static_weights")
        w = self.static_weights * state.active[None, :].astype(jnp.float32)
        w = w / jnp.maximum(w.sum(1, keepdims=True), 1e-9)
        n = w.shape[0]
        return graph_mod.CollaborationGraph(
            neighbors=jnp.zeros((n, 0), jnp.int32),  # static; not re-derived
            weights=w, candidates=state.active)

    def receivers(self, state, graph) -> jnp.ndarray:
        """A client whose static edges all point at never-joined peers
        gets an all-zero row — the server skips its downlink payload."""
        w = graph_mod.selection_matrix(graph)
        return state.active & (w.sum(axis=1) > 0)
