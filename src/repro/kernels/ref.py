"""Pure-jnp oracles for every Pallas kernel in this package.

Conventions: messengers are LOG-probabilities ``logp (N, R, C)`` (numerically
safer on the wire than probabilities — see DESIGN.md §3). All reductions in
fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_kl_ref(logp: jnp.ndarray) -> jnp.ndarray:
    """Eq. 2: D[n,m] = (1/R) sum_{j} KL(s^n_j || s^m_j), logp (N,R,C) -> (N,N).

    KL(p_n || p_m) = sum_c p_n (logp_n - logp_m)
                   = rowterm(n) - <p_n, logp_m>  with rowterm = sum p_n logp_n
    """
    n, r, c = logp.shape
    lp = logp.astype(jnp.float32)
    p = jnp.exp(lp)
    pf = p.reshape(n, r * c)
    lf = lp.reshape(n, r * c)
    rowterm = jnp.sum(pf * lf, axis=-1)                     # (N,)
    cross = pf @ lf.T                                       # (N,N)
    return (rowterm[:, None] - cross) / r


def pairwise_kl_pair_ref(logp_a: jnp.ndarray,
                         logp_b: jnp.ndarray) -> jnp.ndarray:
    """Rectangular Eq. 2 strip: D[a,b] = (1/R) sum_j KL(A_a_j || B_b_j).

    logp_a (U,R,C), logp_b (M,R,C) -> (U,M). The square matrix is the
    A == B special case; the delta path computes only the u×N / N×u strips
    touched by u fresh uploads.
    """
    u, r, c = logp_a.shape
    la = logp_a.astype(jnp.float32).reshape(u, r * c)
    lb = logp_b.astype(jnp.float32).reshape(logp_b.shape[0], r * c)
    pa = jnp.exp(la)
    rowterm = jnp.sum(pa * la, axis=-1)                     # (U,)
    cross = pa @ lb.T                                       # (U,M)
    return (rowterm[:, None] - cross) / r


def int8_dequant_ref(q: jnp.ndarray, scale: jnp.ndarray,
                     zp: jnp.ndarray) -> jnp.ndarray:
    """Int8 wire form -> normalized log-probs, fully materialized.

    q (..., R, C) uint8 codes, scale/zp (..., R) per-row affine params
    (``repro.core.wire.Int8``). The per-row additive ``zp`` cancels in
    the softmax normalization but is applied anyway so the oracle mirrors
    the codec's decode exactly.
    """
    deq = (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
           + zp.astype(jnp.float32)[..., None])
    return jax.nn.log_softmax(deq, axis=-1)


def int8_pairwise_kl_ref(q: jnp.ndarray, scale: jnp.ndarray,
                         zp: jnp.ndarray) -> jnp.ndarray:
    """Eq. 2 divergence matrix of an int8-encoded repository.

    The oracle for the fused dequant->KL kernel: dequantize the whole
    (N,R,C) stack to fp32 log-probs, then the dense pairwise KL. The
    Pallas kernel computes the same matrix without ever materializing
    the fp32 decode in HBM.
    """
    return pairwise_kl_ref(int8_dequant_ref(q, scale, zp))


def int8_pairwise_kl_pair_ref(qa: jnp.ndarray, sa: jnp.ndarray,
                              zpa: jnp.ndarray, qb: jnp.ndarray,
                              sb: jnp.ndarray,
                              zpb: jnp.ndarray) -> jnp.ndarray:
    """Rectangular Eq. 2 strip between two int8-encoded stacks.

    qa (U,R,C) / qb (M,R,C) uint8 codes with per-row affine params ->
    (U,M) fp32. The oracle for the rectangular fused dequant->KL kernel:
    dequantize both sides, then the rectangular strip. The square matrix
    is the a == b special case; the IVF neighbor search computes only
    upload-vs-candidate strips off the wire form.
    """
    return pairwise_kl_pair_ref(int8_dequant_ref(qa, sa, zpa),
                                int8_dequant_ref(qb, sb, zpb))


def soft_ce_ref(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Eq. 1 quality: g[n] = sum_i H(softmax(logits[n,i]), y_i).

    logits (N,R,C) raw client outputs on the reference set; labels (R,) int32.
    """
    z = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(z, axis=-1)                      # (N,R)
    picked = jnp.take_along_axis(z, labels[None, :, None], axis=-1)[..., 0]
    return jnp.sum(lse - picked, axis=-1)                   # (N,)


def neighbor_mean_ref(w: jnp.ndarray, probs: jnp.ndarray) -> jnp.ndarray:
    """Eq. 5 targets: T[n] = sum_m w[n,m] * probs[m]; w rows sum to 1.

    w (N,N) fp32 selection weights (1/K on the K chosen neighbors);
    probs (N,R,C) messenger probabilities -> targets (N,R,C) fp32.
    """
    n, r, c = probs.shape
    pf = probs.astype(jnp.float32).reshape(n, r * c)
    t = w.astype(jnp.float32) @ pf
    return t.reshape(n, r, c)


def neighbor_mean_sparse_ref(neighbors: jnp.ndarray, edge_weights: jnp.ndarray,
                             probs: jnp.ndarray) -> jnp.ndarray:
    """K-sparse Eq. 5: T[n] = sum_k edge_weights[n,k] * probs[neighbors[n,k]].

    neighbors (N,K) int ids (clamped into [0, N)), edge_weights (N,K);
    probs (N,R,C) -> targets (N,R,C) fp32, an elementwise fp32 sum.
    """
    n, r, c = probs.shape
    pf = probs.astype(jnp.float32).reshape(n, r * c)
    rows = pf[jnp.clip(neighbors, 0, n - 1)]                # (N,K,RC)
    t = jnp.sum(edge_weights.astype(jnp.float32)[..., None] * rows, axis=1)
    return t.reshape(n, r, c)
