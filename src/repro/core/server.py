"""The SQMD central server (Algorithm 1 lines 5–10).

State (a pytree — jit-able end to end):
  repo_logp (N,R,C)  messenger repository S (stale rows allowed: asynchrony)
  active    (N,)     participation mask (clients that have ever joined)
  quality   (N,)     latest Eq.1 grades
  round     ()       round counter
  div_cache (N,N)    cached Eq.2 divergence matrix of the CURRENT
                     repository — the delta path scatters u×N / N×u strips
                     into it per trigger instead of rebuilding O(N²·R·C)

``server_round`` consumes freshly uploaded messengers, updates the
repository, re-grades, rebuilds the dynamic graph per the protocol, and
returns the per-client distillation targets (the K^n payloads). The
round's collaboration graph is not state: it is rebuilt every round and
kept by its caller (``ServerBus.last_graph``); SQMD's is K-sparse, so no
(N,N) selection matrix exists on the server, and no (N,N) similarity:
``div_cache`` is the only (N,N) array the server holds.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.core import quality as quality_mod
from repro.core import wire
from repro.core.protocols import Protocol
from repro.obs import host_read, span


class ServerState(NamedTuple):
    repo_logp: jnp.ndarray
    active: jnp.ndarray
    quality: jnp.ndarray
    round: jnp.ndarray
    div_cache: jnp.ndarray


def init_server(n_clients: int, ref_size: int, n_classes: int) -> ServerState:
    """Repository starts uniform (max-entropy messengers => worst quality,
    so un-joined clients are naturally excluded from Q)."""
    uniform = jnp.full((n_clients, ref_size, n_classes),
                       -jnp.log(n_classes), jnp.float32)
    return ServerState(
        repo_logp=uniform,
        active=jnp.zeros((n_clients,), bool),
        quality=jnp.full((n_clients,), quality_mod.BIG),
        round=jnp.zeros((), jnp.int32),
        # the all-uniform repository has KL(p||p) = 0 everywhere, so the
        # zero matrix IS the exact divergence of the initial repository
        div_cache=jnp.zeros((n_clients, n_clients), jnp.float32),
    )


def upload_messengers(state: ServerState,
                      messengers_logp: Union[jnp.ndarray, wire.Payload],
                      uploaded: jnp.ndarray) -> ServerState:
    """Merge fresh messengers into the repository (rows where uploaded).

    ``messengers_logp`` may be a raw (N,R,C) log-prob stack or an encoded
    ``wire.Payload`` — the wire form is decoded ON ingest, so the
    repository always holds what the clients' codec actually delivered
    (dense32 reproduces the raw array bit-for-bit). Clients that skipped
    this round keep their STALE repository row — the paper's
    asynchronous semantics."""
    if isinstance(messengers_logp, wire.Payload):
        up_np = host_read(uploaded, "deliver.mask", bool)
        rows = np.nonzero(up_np)[0]
        if (len(messengers_logp.shape) == 3
                and messengers_logp.shape[0] == up_np.size
                and rows.size < up_np.size):
            # sparse merge: decode ONLY the uploading rows — codecs are
            # row-independent, so this is the same reconstruction at
            # O(u·R·C) instead of O(N·R·C) per delivery
            if rows.size == 0:
                return state._replace(active=state.active
                                      | jnp.asarray(up_np))
            dec = wire.decode(wire.gather(messengers_logp, rows))
            repo = state.repo_logp.at[jnp.asarray(rows)].set(
                dec.astype(jnp.float32))
            return state._replace(repo_logp=repo,
                                  active=state.active | jnp.asarray(up_np))
        messengers_logp = wire.decode(messengers_logp)
    mask = uploaded[:, None, None]
    repo = jnp.where(mask, messengers_logp.astype(jnp.float32),
                     state.repo_logp)
    return state._replace(repo_logp=repo, active=state.active | uploaded)


STALENESS_BINS: Tuple[float, ...] = (0.0, 1.0, 2.0, 4.0, 8.0)


def staleness_summary(last_upload_t: np.ndarray, active: np.ndarray,
                      now: float,
                      bins: Sequence[float] = STALENESS_BINS) -> dict:
    """Histogram of repository-row staleness at virtual time ``now``.

    A row's staleness is the age of its newest merged messenger
    (``now - last_upload_t``); rows of clients that never uploaded are
    excluded. Stale rows stay in the repository (merged, never dropped),
    so this is the distribution the dynamic graph actually grades over.
    Returns plain-python values (JSON-serializable for run summaries).

    The serving side measures the same quantity per RESPONSE:
    ``repro.serve.SnapshotStore`` stamps each published snapshot with its
    virtual publish time, and every answer reports ``now -
    published_at`` — model-staleness in these same virtual-time units,
    where this histogram covers repository rows."""
    last = np.asarray(last_upload_t, float)
    ages = now - last[np.asarray(active, bool) & np.isfinite(last)]
    edges = list(bins) + [np.inf]
    if ages.size == 0:
        return {"n": 0, "mean": 0.0, "max": 0.0, "n_stale": 0,
                "hist": [0] * (len(edges) - 1), "bin_edges": list(bins)}
    hist, _ = np.histogram(ages, bins=edges)
    return {"n": int(ages.size), "mean": float(ages.mean()),
            "max": float(ages.max()), "n_stale": int((ages > 1e-9).sum()),
            "hist": [int(h) for h in hist], "bin_edges": list(bins)}


def policy_round(state: ServerState, policy, ref_labels: jnp.ndarray,
                 uploaded: Optional[np.ndarray] = None):
    """Lines 7–10, policy-agnostic: grade -> build graph -> emit targets.

    ``policy`` is a resolved ServerPolicy instance. Returns
    (new_state, targets (N,R,C) fp32, CollaborationGraph) — the graph is
    what the engine's metrics/graph-stats read.

    ``uploaded``, when given, is the boolean (N,) mask of every repository
    row that changed since the last policy round: the policy may then take
    its incremental O(u·N) graph-update path (``build_graph_delta``)
    instead of the O(N²) full rebuild. ``uploaded=None`` (the default, and
    the legacy ``server_round`` contract) always rebuilds from scratch."""
    with span("repro.grade"):
        g = policy.grade(state, ref_labels)
    if uploaded is None:
        with span("repro.build_graph", path="full"):
            graph = policy.build_graph(state, g)
    else:
        path = "ivf" if policy.selection == "ivf" else "delta"
        with span("repro.build_graph", path=path):
            graph = policy.build_graph_delta(state, g, uploaded)
    path = "dense" if graph.edge_weights is None else "k-sparse"
    with span("repro.emit_targets", path=path):
        targets = policy.emit_targets(state, graph)
    return policy.update_state(state, g, graph), targets, graph


def server_round(state: ServerState, protocol: Union[Protocol, "ServerPolicy",
                                                     str],
                 ref_labels: jnp.ndarray,
                 static_weights: Optional[jnp.ndarray] = None
                 ) -> Tuple[ServerState, jnp.ndarray]:
    """Lines 7–10: one server round under any registered policy.

    ``protocol`` may be a Protocol config, a registered policy name, or a
    ServerPolicy instance. Returns (new_state, targets (N,R,C) fp32).
    For "ddist" pass the static graph's ``static_weights`` (or use a
    pre-``setup`` DDistPolicy instance)."""
    from repro.core.policies import as_policy
    pol = as_policy(protocol, static_weights=static_weights)
    new, targets, _ = policy_round(state, pol, ref_labels)
    return new, targets
