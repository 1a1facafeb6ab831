"""The ``ServerPolicy`` strategy interface + registry.

A policy is the server-side collaboration strategy of Algorithm 1 lines
7-10, split into three overridable stages:

  grade(state, ref_labels)        -> (N,) quality scores       (Eq. 1)
  build_graph(state, quality)     -> CollaborationGraph        (Defs. 4-5)
  emit_targets(state, graph)      -> (N,R,C) distill targets   (Eq. 5)

``server_round``/``FederationEngine`` are policy-agnostic: they call these
three hooks and never inspect the protocol name. New strategies drop in as

    @register_policy("my-policy")
    class MyPolicy(ServerPolicy):
        def build_graph(self, state, quality): ...

and become constructible from ``Protocol("my-policy")``, the engine, and
the launch CLI without touching the core loop.
"""
from __future__ import annotations

import abc
from typing import Dict, Optional, Tuple, Type, Union

import jax.numpy as jnp

from repro.core import quality as quality_mod
from repro.kernels import ops

_REGISTRY: Dict[str, Type["ServerPolicy"]] = {}


def register_policy(name: str):
    """Class decorator: ``@register_policy("sqmd")`` binds ``cls.name`` and
    makes the policy reachable by name everywhere (Protocol, engine, CLI)."""

    def deco(cls: Type["ServerPolicy"]) -> Type["ServerPolicy"]:
        if not isinstance(name, str) or not name:
            raise ValueError(f"policy name must be a non-empty str: {name!r}")
        if name in _REGISTRY:
            raise ValueError(f"policy {name!r} already registered "
                             f"({_REGISTRY[name].__qualname__})")
        if not (isinstance(cls, type) and issubclass(cls, ServerPolicy)):
            raise TypeError(f"@register_policy expects a ServerPolicy "
                            f"subclass, got {cls!r}")
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def unregister_policy(name: str) -> None:
    """Remove a policy (test teardown helper)."""
    _REGISTRY.pop(name, None)


def registered_policies() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def is_registered(name: str) -> bool:
    return name in _REGISTRY


def get_policy(name: str) -> Type["ServerPolicy"]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown policy {name!r}; registered: "
                       f"{registered_policies()}") from None


def as_policy(policy: Union[str, "ServerPolicy", "Protocol"],  # noqa: F821
              static_weights: Optional[jnp.ndarray] = None) -> "ServerPolicy":
    """Coerce a policy instance / Protocol config / name into a policy.

    ``static_weights`` is forwarded to policies that carry a static graph
    (D-Dist) — the legacy ``server_round(..., static_weights=...)`` path."""
    if isinstance(policy, ServerPolicy):
        pol = policy
    elif isinstance(policy, str):
        pol = get_policy(policy)()
    else:  # a Protocol config
        pol = get_policy(policy.name)(policy)
    supports_static = (type(pol).attach_static_weights
                       is not ServerPolicy.attach_static_weights)
    if static_weights is not None and supports_static:
        # policies without a static graph ignore the argument, matching the
        # legacy server_round(..., static_weights=...) contract
        pol.attach_static_weights(static_weights)
    return pol


class ServerPolicy(abc.ABC):
    """Base strategy. Subclasses override ``build_graph`` (required) and
    optionally ``grade`` / ``emit_targets`` / ``setup``.

    Policies are lightweight config holders — all array math flows through
    the three hooks. Their kernels dispatch by ``ops``' own process-level
    setting (``$REPRO_KERNEL_BACKEND``, else the platform default), so no
    hook takes a backend argument.
    """

    name: str = "?"                 # bound by @register_policy
    uses_reference: bool = True     # False => no messengers, no server round
    # Client device mesh (repro.sharding.make_client_mesh), attached by the
    # ServerBus when the engine runs device-sharded: policies whose graph
    # build scales with the population (SQMD's O(N²·R·C) divergence) shard
    # it row-wise over this mesh. An ATTRIBUTE rather than a hook kwarg so
    # third-party build_graph overrides keep their signature.
    mesh = None
    # Neighbor-selection strategy, attached by the ServerBus the same way
    # as ``mesh``: "exact" keeps the dense (N,N) divergence path; "ivf"
    # lets policies that support it (SQMD) switch their delta rounds to
    # the approximate NeighborIndex — sub-quadratic state and per-upload
    # cost for million-client graphs. Policies without an approximate
    # path simply never read it.
    selection = "exact"

    def __init__(self, protocol: Optional["Protocol"] = None):  # noqa: F821
        if protocol is None:
            from repro.core.protocols import Protocol
            protocol = Protocol(self.name)
        self.protocol = protocol

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.protocol})"

    # -- config passthroughs the engine needs -----------------------------
    @property
    def rho(self) -> float:
        return self.protocol.rho

    @property
    def interval(self) -> int:
        return self.protocol.interval

    # -- lifecycle ---------------------------------------------------------
    def setup(self, key, n_clients: int) -> None:
        """One-time hook at federation build (e.g. D-Dist draws its static
        random graph here). Default: nothing."""

    def attach_static_weights(self, weights: jnp.ndarray) -> None:
        """Inject a pre-built static graph; only meaningful for policies
        that carry one (D-Dist overrides)."""
        raise ValueError(f"policy {self.name!r} takes no static graph")

    # -- the three stages of a server round --------------------------------
    def grade(self, state, ref_labels: jnp.ndarray) -> jnp.ndarray:
        """(N,) Eq.1 quality grades of the repository messengers."""
        return quality_mod.quality_scores(state.repo_logp, ref_labels)

    @abc.abstractmethod
    def build_graph(self, state, quality: jnp.ndarray):
        """CollaborationGraph for this round (the policy's whole point)."""

    def build_graph_delta(self, state, quality: jnp.ndarray, uploaded):
        """Incremental variant: ``uploaded`` is the (N,) bool mask of every
        repository row that changed since the last policy round. Policies
        whose round cost scales with the population (sqmd's O(N²·R·C)
        divergence matrix) override this to pay only O(u·N); the default
        ignores the mask and rebuilds — always correct, never required."""
        return self.build_graph(state, quality)

    def emit_targets(self, state, graph) -> jnp.ndarray:
        """(N,R,C) fp32 probability targets: the K^n neighbor mean.

        The runtime wire-codes this output with the downlink codec
        before it reaches any client (``ServerBus.fire``) — the rows
        that actually ship are ``receivers``. A K-sparse graph (one that
        carries ``edge_weights``) gathers its K neighbors per client; a
        dense one multiplies by its (N,N) ``weights``."""
        probs = jnp.exp(state.repo_logp)
        if graph.edge_weights is not None:
            return ops.neighbor_mean(graph.neighbors, graph.edge_weights,
                                     probs)
        return ops.neighbor_mean_dense(graph.weights, probs)

    def receivers(self, state, graph) -> jnp.ndarray:
        """(N,) bool — which clients a K^n downlink payload is sent to
        (the rows charged wire bytes). Default: every participating
        client, per the paper ('any client, regardless of its quality,
        is assigned K neighbors'). Policies that emit nothing (I-SGD)
        or skip edge-less rows (D-Dist) override."""
        return state.active

    # -- state fold-in -----------------------------------------------------
    def update_state(self, state, quality: jnp.ndarray, graph):
        """Fold this round's results into the ServerState: the grades, the
        round counter, and — where the graph carries the divergence it
        was built from — ``div_cache`` (both the full rebuild and the
        delta scatter produce it, so the cache always matches the current
        repository)."""
        div = (graph.divergence if graph.divergence is not None
               else state.div_cache)
        return state._replace(quality=quality, div_cache=div,
                              round=state.round + 1)
