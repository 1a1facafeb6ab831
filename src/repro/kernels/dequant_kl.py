"""Pallas TPU kernel: fused dequant -> pairwise messenger KL (Eq. 2) for
int8-encoded repositories.

The server's graph math wants the (N,N) divergence matrix of whatever the
repository holds; when messengers arrive int8-quantized (``wire.Int8``)
the naive route decodes the whole stack to fp32 — an (N,R,C) HBM
materialization 4x the wire form. This kernel dequantizes per-tile in
VMEM instead: HBM holds the uint8 codes plus O(N·R) fp32 row statistics,
and each grid step reconstructs only its (block, C, BR) tiles. The
wrapper lays the codes out as (N, C, R), one uint8 copy of the wire form,
so R fills the 128-wide lanes and the (R, C) contraction runs as C
lane-dense matmuls.

Math: with deq = q·scale + zp, the normalized log-prob is
logp = deq − logsumexp(deq) = q·scale − lse(q·scale) − the per-row zp is
an additive shift that cancels in the softmax, so the kernel needs only
``q``, ``scale``, and the precomputed ``lse`` of the scaled codes. The
grid is (N/BN, M/BM, R/BR) with the row axis innermost: each (i, j)
output tile accumulates Σ_r Σ_c p_n (logp_n − logp_m) in fp32 in VMEM,
row-entropy term fused into the same loop (as in ``pairwise_kl``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret
from repro.kernels.pairwise_kl import _ceil_mult

DEFAULT_BN = 128
DEFAULT_BM = 128
DEFAULT_BR = 128

_LSE_PAD = 1e30     # padded rows: p = exp(deq - LSE_PAD) == 0
_STATS_CHUNK = 256  # row-stats pass: bounds the fp32 dequant to
#                     (chunk, R, C) — never the full stack


def _kernel(qa_ref, sa_ref, la_ref, qb_ref, sb_ref, lb_ref, out_ref, *,
            n_r: int, inv_r: float):
    """qa (BN,C,BR) uint8 codes [i,r]; sa/la (BN,1,BR) scale/lse [i,r];
    qb/sb/lb the [j,r] tiles; out (BN,BM) fp32 accumulator."""
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Mosaic has no uint8 -> f32 cast; widen through int32
    lpa = (qa_ref[...].astype(jnp.int32).astype(jnp.float32)
           * sa_ref[...] - la_ref[...])                     # (BN,C,BR)
    pa = jnp.exp(lpa)
    lpb = (qb_ref[...].astype(jnp.int32).astype(jnp.float32)
           * sb_ref[...] - lb_ref[...])                     # (BM,C,BR)
    rowterm = jnp.sum(jnp.sum(pa * lpa, axis=1), axis=1,
                      keepdims=True)                        # (BN,1)
    # the (C, R) contraction as C lane-dense (BN,BR)x(BM,BR)^T matmuls
    cross = sum(jax.lax.dot_general(
        pa[:, c, :], lpb[:, c, :], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
        for c in range(pa.shape[1]))                        # (BN,BM)
    out_ref[...] += rowterm - cross

    @pl.when(r == n_r - 1)
    def _scale():
        out_ref[...] *= inv_r


def int8_row_stats(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """lse[n,r] = logsumexp_c(q[n,r,c] * scale[n,r]) in bounded chunks.

    The only fp32 dequant outside the kernel, and it is (chunk, R, C) at
    a time — O(N·R) output, never an (N,R,C) resident decode."""
    n = q.shape[0]
    outs = []
    for i in range(0, n, _STATS_CHUNK):
        deq = (q[i:i + _STATS_CHUNK].astype(jnp.float32)
               * scale[i:i + _STATS_CHUNK].astype(jnp.float32)[..., None])
        outs.append(jax.nn.logsumexp(deq, axis=-1))
    return jnp.concatenate(outs, axis=0)


def _pad_operand(q, scale, lse, rows_pad, r_pad):
    """Lay one wire-form operand out as (rows, C, R) codes and (rows, 1,
    R) row statistics, padded along its row/ref axes. Padded rows get
    lse = _LSE_PAD => p = 0 and the (finite) -_LSE_PAD log-prob is
    annihilated by it; padded rows are sliced off the output."""
    q_p = jnp.pad(jnp.swapaxes(q, 1, 2), ((0, rows_pad), (0, 0), (0, r_pad)))
    s_p = jnp.pad(scale.astype(jnp.float32), ((0, rows_pad), (0, r_pad)))
    l_p = jnp.pad(lse.astype(jnp.float32), ((0, rows_pad), (0, r_pad)),
                  constant_values=_LSE_PAD)
    return q_p, s_p[:, None, :], l_p[:, None, :]


@functools.partial(jax.jit, static_argnames=("bn", "bm", "br", "interpret"))
def _call_pair(qa, sa, la, qb, sb, lb, bn, bm, br, interpret):
    """Rectangular strip off two (possibly aliased) wire-form operands:
    qa (U,R,C) x qb (M,R,C) -> (U,M). The square matrix passes the same
    arrays for both sides."""
    u, r, c = qa.shape
    m = qb.shape[0]
    bn = min(bn, _ceil_mult(u))
    bm = min(bm, _ceil_mult(m))
    br = min(br, r)
    u_pad = -u % bn
    m_pad = -m % bm
    r_pad = -r % br
    qa_p, sa_p, la_p = _pad_operand(qa, sa, la, u_pad, r_pad)
    qb_p, sb_p, lb_p = _pad_operand(qb, sb, lb, m_pad, r_pad)
    gn, gm, gr = (u + u_pad) // bn, (m + m_pad) // bm, (r + r_pad) // br

    out = pl.pallas_call(
        functools.partial(_kernel, n_r=gr, inv_r=1.0 / r),
        grid=(gn, gm, gr),
        in_specs=[
            pl.BlockSpec((bn, c, br), lambda i, j, r: (i, 0, r)),  # q  [i]
            pl.BlockSpec((bn, 1, br), lambda i, j, r: (i, 0, r)),  # s  [i]
            pl.BlockSpec((bn, 1, br), lambda i, j, r: (i, 0, r)),  # lse[i]
            pl.BlockSpec((bm, c, br), lambda i, j, r: (j, 0, r)),  # q  [j]
            pl.BlockSpec((bm, 1, br), lambda i, j, r: (j, 0, r)),  # s  [j]
            pl.BlockSpec((bm, 1, br), lambda i, j, r: (j, 0, r)),  # lse[j]
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((u + u_pad, m + m_pad), jnp.float32),
        interpret=interpret,
        name="int8_pairwise_kl",
    )(qa_p, sa_p, la_p, qb_p, sb_p, lb_p)
    return out[:u, :m]


def _call(q, scale, lse, bn, bm, br, interpret):
    return _call_pair(q, scale, lse, q, scale, lse, bn, bm, br, interpret)


def int8_pairwise_kl(q: jnp.ndarray, scale: jnp.ndarray, zp: jnp.ndarray,
                     bn: int = DEFAULT_BN, bm: int = DEFAULT_BM,
                     br: int = DEFAULT_BR,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """q (N,R,C) uint8, scale/zp (N,R) -> (N,N) fp32 divergence matrix.

    ``zp`` is accepted for API symmetry with the wire form but never read:
    a per-row additive shift cancels in the softmax normalization.
    ``interpret`` defaults from the platform (compiled on TPU,
    interpreter elsewhere)."""
    del zp
    interpret = resolve_interpret(interpret)
    if q.ndim != 3 or scale.shape != q.shape[:2]:
        raise ValueError(f"shapes disagree: q {q.shape}, scale "
                         f"{scale.shape}")
    lse = int8_row_stats(q, scale)
    return _call(q, scale, lse, bn, bm, br, interpret)


def int8_pairwise_kl_pair(qa: jnp.ndarray, sa: jnp.ndarray,
                          zpa: jnp.ndarray, qb: jnp.ndarray,
                          sb: jnp.ndarray, zpb: jnp.ndarray,
                          bn: int = DEFAULT_BN, bm: int = DEFAULT_BM,
                          br: int = DEFAULT_BR,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Rectangular Eq.2 strip straight from two int8 wire forms.

    qa (U,R,C) / qb (M,R,C) uint8 codes, per-row affine params -> (U,M)
    fp32. The IVF neighbor-search primitive: an upload's divergence
    strips against candidate-cluster members are computed off the stored
    wire form, never a dense fp32 decode. ``zpa``/``zpb`` are accepted
    for wire-form API symmetry but never read (the per-row shift cancels
    in the softmax)."""
    del zpa, zpb
    interpret = resolve_interpret(interpret)
    if qa.ndim != 3 or sa.shape != qa.shape[:2]:
        raise ValueError(f"shapes disagree: qa {qa.shape}, sa {sa.shape}")
    if qb.ndim != 3 or sb.shape != qb.shape[:2]:
        raise ValueError(f"shapes disagree: qb {qb.shape}, sb {sb.shape}")
    if qa.shape[1:] != qb.shape[1:]:
        raise ValueError(f"operands disagree on (R, C): qa {qa.shape}, "
                         f"qb {qb.shape}")
    la = int8_row_stats(qa, sa)
    lb = int8_row_stats(qb, sb)
    return _call_pair(qa, sa, la, qb, sb, lb, bn, bm, br, interpret)
