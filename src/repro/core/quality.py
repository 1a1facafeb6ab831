"""Model quality (paper Def. 3, Eq. 1) and the top-Q candidate filter.

The server holds the reference labels; each client's grade is the summed
cross-entropy of its messenger. The Q lowest-loss ACTIVE clients form the
candidate pool Q — newcomers/malicious clients are ruled out of the
downstream similarity step, but (paper §III-A) every client still RECEIVES
K neighbors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops

BIG = jnp.float32(1e30)


def quality_scores(messengers_logp: jnp.ndarray,
                   ref_labels: jnp.ndarray) -> jnp.ndarray:
    """g (N,) — Eq.1 summed CE of each messenger vs the server's labels.

    Messengers are log-probs; soft_ce works on raw scores and log-probs
    alike (logsumexp(logp) = 0 exactly, so CE = -logp[y])."""
    return ops.soft_ce(messengers_logp, ref_labels)


def candidate_mask(quality: jnp.ndarray, active: jnp.ndarray,
                   q: int) -> jnp.ndarray:
    """Boolean (N,) mask of the Q lowest-loss active clients.

    Inactive clients are pushed to +inf so they never enter Q. Ties are
    broken by client index (stable top_k). ``q`` counts are honored even if
    fewer than q clients are active (mask then covers all active ones)."""
    scores = jnp.where(active, quality, BIG)
    n = quality.shape[0]
    q = min(q, n)
    _, idx = jax.lax.top_k(-scores, q)
    mask = jnp.zeros((n,), bool).at[idx].set(True)
    return mask & active
