"""FedMD baseline (Li & Wang 2019): everyone distills toward the global
average messenger — the Q = K = N degenerate case of SQMD."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import graph as graph_mod
from repro.core.policies.base import ServerPolicy, register_policy


@register_policy("fedmd")
class FedMDPolicy(ServerPolicy):
    """Complete graph over active clients, uniform weights."""

    def build_graph(self, state, quality: jnp.ndarray):
        # already O(N) per round: the base build_graph_delta fallback
        # (ignore the uploaded mask, rebuild) IS FedMD's delta path
        return graph_mod.fedmd_graph(state.active)
