"""Pallas TPU kernel: K-neighbor mean distillation targets (paper Eq. 5).

T = W · S_flat where W (N,N) is the row-stochastic top-K selection matrix
(1/K at the chosen neighbors) and S_flat (N, R·C) the messenger
probabilities. A blocked matmul with grid (N/BN, RC/BK, N/BJ), j innermost
accumulating each (i, k) output tile in fp32 in VMEM. W is tiny relative to
S, so tiles of W stay resident while S streams through.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

DEFAULT_BN = 128
DEFAULT_BJ = 128
DEFAULT_BK = 512


def _kernel(w_ref, s_ref, out_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.float32)          # (BN, BJ)
    s = s_ref[...].astype(jnp.float32)          # (BJ, BK)
    out_ref[...] += jax.lax.dot_general(
        w, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bn", "bj", "bk", "interpret"))
def neighbor_mean(w: jnp.ndarray, probs: jnp.ndarray, bn: int = DEFAULT_BN,
                  bj: int = DEFAULT_BJ, bk: int = DEFAULT_BK,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """w (N,N) selection weights, probs (N,R,C) -> targets (N,R,C) fp32.

    ``interpret`` defaults from the platform (compiled on TPU, interpreter
    elsewhere)."""
    interpret = resolve_interpret(interpret)  # static: trace-time resolve
    n, r, c = probs.shape
    s = probs.reshape(n, r * c)
    rc = r * c
    bn = min(bn, n)
    bj = min(bj, n)
    bk = min(bk, rc)
    n_pad = -n % bn
    j_pad = -n % bj
    k_pad = -rc % bk
    w_p = jnp.pad(w, ((0, n_pad), (0, j_pad)))
    s_p = jnp.pad(s, ((0, j_pad), (0, k_pad)))
    gn, gk, gj = (n + n_pad) // bn, (rc + k_pad) // bk, (n + j_pad) // bj

    out = pl.pallas_call(
        _kernel,
        grid=(gn, gk, gj),
        in_specs=[
            pl.BlockSpec((bn, bj), lambda i, k, j: (i, j)),
            pl.BlockSpec((bj, bk), lambda i, k, j: (j, k)),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda i, k, j: (i, k)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, rc + k_pad), jnp.float32),
        interpret=interpret,
        name="neighbor_mean",
    )(w_p, s_p)
    return out[:n, :rc].reshape(n, r, c)
