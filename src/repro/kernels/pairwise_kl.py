"""Pallas TPU kernel: pairwise messenger KL-divergence matrix (paper Eq. 2).

The O(N²·R·C) server hot spot, decomposed for the MXU (DESIGN.md §4):

    D[n,m] = (rowterm(n) − P_flat[n] · L_flat[m]) / R

i.e. a blocked matmul over the flattened (R·C) axis with a fused
negative-entropy row term. Grid is (N/BN, M/BM, RC/BK): the k axis is
innermost so each (i, j) output tile accumulates in VMEM in fp32; the row
term is fused into the same k loop (it reads the (i, k) tile of L that is
already resident). Block shapes default to MXU-aligned 128×128×512.

``pairwise_kl_pair`` is the rectangular generalization: divergence strips
D[a, b] between two DIFFERENT messenger stacks A (U,R,C) and B (N,R,C).
It is the delta-update primitive for the server's incremental graph
rebuild — after u uploads only the u×N and N×u strips change, so the
server pays O(u·N·R·C) instead of O(N²·R·C) per trigger.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# re-exported for back-compat: default_interpret lived here before the
# shared backend module existed
from repro.kernels.backend import default_interpret, resolve_interpret

DEFAULT_BN = 128
DEFAULT_BM = 128
DEFAULT_BK = 512


def _kernel(p_ref, ln_ref, lm_ref, out_ref, *, n_k: int, inv_r: float):
    """p_ref (BN,BK) probs tile [i,k]; ln_ref (BN,BK) logp tile [i,k];
    lm_ref (BM,BK) logp tile [j,k]; out_ref (BN,BM) fp32 accumulator."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    p = p_ref[...].astype(jnp.float32)
    ln = ln_ref[...].astype(jnp.float32)
    lm = lm_ref[...].astype(jnp.float32)
    # fused row entropy term: sum_k p * ln  (broadcast over the m tile)
    rowterm = jnp.sum(p * ln, axis=1, keepdims=True)        # (BN, 1)
    cross = jax.lax.dot_general(
        p, lm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (BN, BM)
    out_ref[...] += rowterm - cross

    @pl.when(k == n_k - 1)
    def _scale():
        out_ref[...] *= inv_r


@functools.partial(jax.jit,
                   static_argnames=("r", "bn", "bm", "bk", "interpret"))
def _pair_call(lp_a: jnp.ndarray, lp_b: jnp.ndarray, r: int, bn: int,
               bm: int, bk: int, interpret: bool) -> jnp.ndarray:
    """Flattened strips: lp_a (U,RC), lp_b (M,RC) -> (U,M) fp32."""
    u, rc = lp_a.shape
    m = lp_b.shape[0]
    p_a = jnp.exp(lp_a.astype(jnp.float32)).astype(lp_a.dtype)
    bn = min(bn, _ceil_mult(u))
    bm = min(bm, _ceil_mult(m))
    bk = min(bk, _ceil_mult(rc))
    n_pad = -u % bn
    m_pad = -m % bm
    k_pad = -rc % bk
    # zero-pad: padded k columns contribute 0 to both terms (p=0);
    # padded rows/cols are sliced off below.
    p_p = jnp.pad(p_a, ((0, n_pad), (0, k_pad)))
    la_p = jnp.pad(lp_a, ((0, n_pad), (0, k_pad)))
    lb_p = jnp.pad(lp_b, ((0, m_pad), (0, k_pad)))
    gn, gm, gk = (u + n_pad) // bn, (m + m_pad) // bm, (rc + k_pad) // bk

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=gk, inv_r=1.0 / r),
        grid=(gn, gm, gk),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k: (i, k)),  # P   [i,k]
            pl.BlockSpec((bn, bk), lambda i, j, k: (i, k)),  # L_a [i,k]
            pl.BlockSpec((bm, bk), lambda i, j, k: (j, k)),  # L_b [j,k]
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((u + n_pad, m + m_pad), jnp.float32),
        interpret=interpret,
        name="pairwise_kl",
    )(p_p, la_p, lb_p)
    return out[:u, :m]


def pairwise_kl_pair(logp_a: jnp.ndarray, logp_b: jnp.ndarray,
                     bn: int = DEFAULT_BN, bm: int = DEFAULT_BM,
                     bk: int = DEFAULT_BK,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Rectangular divergence strip: D[a,b] = (1/R) Σ_j KL(A_a_j || B_b_j).

    logp_a (U,R,C), logp_b (M,R,C) -> (U,M) fp32. The square matrix is the
    A == B special case (``pairwise_kl``)."""
    interpret = resolve_interpret(interpret)
    u, r, c = logp_a.shape
    if logp_b.shape[1:] != (r, c):
        raise ValueError(f"messenger shapes disagree: {logp_a.shape} vs "
                         f"{logp_b.shape}")
    return _pair_call(logp_a.reshape(u, r * c),
                      logp_b.reshape(logp_b.shape[0], r * c),
                      r, bn, bm, bk, interpret)


def pairwise_kl(logp: jnp.ndarray, bn: int = DEFAULT_BN, bm: int = DEFAULT_BM,
                bk: int = DEFAULT_BK,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """logp (N,R,C) log-messengers -> (N,N) fp32 divergence matrix.

    ``interpret`` defaults from the platform (compiled on TPU, interpreter
    elsewhere); pass it explicitly to pin a mode."""
    return pairwise_kl_pair(logp, logp, bn=bn, bm=bm, bk=bk,
                            interpret=interpret)


def _ceil_mult(x: int, base: int = 8) -> int:
    """Smallest multiple of ``base`` >= x (keeps tiny test shapes legal)."""
    return -(-x // base) * base
