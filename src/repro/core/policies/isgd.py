"""I-SGD baseline: isolated local SGD — no collaboration, zero targets."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core import graph as graph_mod
from repro.core.policies.base import ServerPolicy, register_policy


@register_policy("isgd")
class ISGDPolicy(ServerPolicy):
    """Empty graph; the engine skips the communication step entirely
    (``uses_reference`` False), but a direct ``server_round`` still yields
    well-defined all-zero targets."""

    uses_reference = False

    def build_graph(self, state, quality: jnp.ndarray, *,
                    backend: Optional[str] = None):
        n = state.active.shape[0]
        return graph_mod.k_sparse(jnp.zeros((n, 0), jnp.int32),
                                  jnp.zeros((n, 0), jnp.float32),
                                  state.sim, state.active)

    def receivers(self, state, graph) -> jnp.ndarray:
        """No collaboration, no downlink: zero wire bytes charged."""
        return jnp.zeros_like(state.active)
