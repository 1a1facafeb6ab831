"""Pallas TPU kernels: K-neighbor mean distillation targets (paper Eq. 5).

``neighbor_mean`` is the K-sparse form: T[n] = sum_k w[n,k] ·
S[nbrs[n,k]], with the (N,K) neighbour ids and edge weights of the
collaboration graph and S (N, R·C) the messenger probabilities. The whole
stack S is read into VMEM once, one (R·C)-wide row per client; each grid
step then sums its BN clients' K rows out of VMEM, with the step's ids
and weights in SMEM, so no (N,N) matrix exists. A stack too large for
VMEM takes the same sum as an XLA gather.

``neighbor_mean_dense`` is T = W · S_flat for graphs that are dense by
nature (FedMD's complete graph, D-Dist's static matrix): a blocked matmul
with grid (N/BN, RC/BK, N/BJ), j innermost accumulating each (i, k)
output tile in fp32 in VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.ref import neighbor_mean_sparse_ref

DEFAULT_BN = 128
DEFAULT_BJ = 128
DEFAULT_BK = 512
DEFAULT_SPARSE_BN = 128
# the resident stack's VMEM ceiling: a v5e core has 128 MiB of VMEM
SPARSE_VMEM_BYTES = 96 << 20
LANES = 128


def _sparse_kernel(ids_ref, w_ref, s_ref, out_ref):
    """ids_ref, w_ref: this step's BN·K ids and edge weights in SMEM;
    s_ref (N, L, 128) the whole stack in VMEM; out_ref (BN, L, 128)."""
    bn = out_ref.shape[0]
    k = ids_ref.shape[0] // bn

    def row(r, carry):
        e = r * k
        acc = w_ref[e] * s_ref[ids_ref[e]]
        for j in range(1, k):
            acc += w_ref[e + j] * s_ref[ids_ref[e + j]]
        out_ref[r] = acc
        return carry

    jax.lax.fori_loop(0, bn, row, 0)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def neighbor_mean(neighbors: jnp.ndarray, edge_weights: jnp.ndarray,
                  probs: jnp.ndarray, bn: int = DEFAULT_SPARSE_BN,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """K-sparse Eq. 5: neighbors (N,K) int ids, edge_weights (N,K),
    probs (N,R,C) -> targets (N,R,C) fp32, summed in fp32.

    Ids are clamped into [0, N) (a gather never leaves the stack);
    zero-weight slots add nothing whatever id they hold. ``interpret``
    defaults from the platform (compiled on TPU, interpreter elsewhere)."""
    interpret = resolve_interpret(interpret)  # static: trace-time resolve
    n, r, c = probs.shape
    k = neighbors.shape[1]
    rc = r * c
    lanes = -(-rc // LANES)
    ids = jnp.clip(neighbors.astype(jnp.int32), 0, n - 1)
    w = edge_weights.astype(jnp.float32)
    # a (L, 128) row fills whole (8, 128) tiles of VMEM
    stack = n * -(-lanes // 8) * 8 * LANES * 4
    if k == 0 or stack > SPARSE_VMEM_BYTES:
        return neighbor_mean_sparse_ref(ids, w, probs)
    bn = min(bn, n)
    n_pad = -n % bn
    steps = (n + n_pad) // bn
    ids = jnp.pad(ids, ((0, n_pad), (0, 0))).reshape(steps, 1, bn * k)
    w = jnp.pad(w, ((0, n_pad), (0, 0))).reshape(steps, 1, bn * k)
    s = jnp.pad(probs.reshape(n, rc).astype(jnp.float32),
                ((0, 0), (0, lanes * LANES - rc))).reshape(n, lanes, LANES)
    step = pl.BlockSpec((None, None, bn * k), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _sparse_kernel,
        grid=(steps,),
        in_specs=[step, step, pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((bn, lanes, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, lanes, LANES),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=stack + (16 << 20)),
        interpret=interpret,
        name="neighbor_mean",
    )(ids, w, s)
    return out[:n].reshape(n, lanes * LANES)[:, :rc].reshape(n, r, c)


def _dense_kernel(w_ref, s_ref, out_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.float32)          # (BN, BJ)
    s = s_ref[...].astype(jnp.float32)          # (BJ, BK)
    out_ref[...] += jax.lax.dot_general(
        w, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bn", "bj", "bk", "interpret"))
def neighbor_mean_dense(w: jnp.ndarray, probs: jnp.ndarray,
                        bn: int = DEFAULT_BN, bj: int = DEFAULT_BJ,
                        bk: int = DEFAULT_BK,
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
        _dense_kernel,
        grid=(gn, gk, gj),
        in_specs=[
            pl.BlockSpec((bn, bj), lambda i, k, j: (i, j)),
            pl.BlockSpec((bj, bk), lambda i, k, j: (j, k)),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda i, k, j: (i, k)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, rc + k_pad), jnp.float32),
        interpret=interpret,
        name="neighbor_mean_dense",
    )(w_p, s_p)
    return out[:n, :rc].reshape(n, r, c)
