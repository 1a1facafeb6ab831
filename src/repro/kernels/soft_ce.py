"""Pallas TPU kernel: messenger quality scores (paper Eq. 1).

g[n] = Σ_i [ logsumexp(z[n,i,:]) − z[n,i,y_i] ]  for raw logits z (N,R,C).

The wrapper lays the logits out as (N, C, R) so the long reference axis R
sits in the 128-wide lanes and the short class axis C in the sublanes.
Grid (N/BN, R/BR); each step loads a (BN, C, BR) logits tile into VMEM,
does a fused max-subtract logsumexp over C and a one-hot label pick
(iota-compare — no gather, VPU-friendly), and accumulates the (BN, 1)
partial sums in the output tile. Never materializes fp32 (N,R,C) in HBM.

TPU block rules: the last two block dims must be multiples of (8, 128) or
the whole array dims, so the output is the 2-D column (N, 1) and the
labels the row (1, R); the default BR is either all of R or 128·k.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

DEFAULT_BN = 8
DEFAULT_BR = 256


def _kernel(z_ref, y_ref, out_ref):
    r_idx = pl.program_id(1)

    @pl.when(r_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    z = z_ref[...].astype(jnp.float32)          # (BN, C, BR)
    y = y_ref[...]                               # (1, BR)
    zmax = jnp.max(z, axis=1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(z - zmax), axis=1)) + zmax[:, 0, :]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, z.shape[1:], 0)
              == y).astype(jnp.float32)                     # (C, BR)
    picked = jnp.sum(z * onehot[None], axis=1)              # (BN, BR)
    # padded rows carry label -1 -> onehot all-zero -> picked 0; their lse
    # is masked out by the label sentinel too:
    valid = (y >= 0).astype(jnp.float32)
    out_ref[...] += jnp.sum((lse - picked) * valid, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bn", "br", "interpret"))
def soft_ce(logits: jnp.ndarray, labels: jnp.ndarray, bn: int = DEFAULT_BN,
            br: int = DEFAULT_BR,
            interpret: Optional[bool] = None) -> jnp.ndarray:
    """logits (N,R,C), labels (R,) int32 -> quality losses (N,) fp32.

    ``interpret`` defaults from the platform (compiled on TPU, interpreter
    elsewhere)."""
    interpret = resolve_interpret(interpret)  # static: trace-time resolve
    n, r, c = logits.shape
    bn = min(bn, n)
    br = min(br, r)
    n_pad = -n % bn
    r_pad = -r % br
    z = jnp.pad(jnp.swapaxes(logits, 1, 2), ((0, n_pad), (0, 0), (0, r_pad)))
    y = jnp.pad(labels.astype(jnp.int32), (0, r_pad),
                constant_values=-1)[None, :]
    gn, gr = (n + n_pad) // bn, (r + r_pad) // br

    out = pl.pallas_call(
        _kernel,
        grid=(gn, gr),
        in_specs=[
            pl.BlockSpec((bn, c, br), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, br), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, 1), jnp.float32),
        interpret=interpret,
        name="soft_ce",
    )(z, y)
    return out[:n, 0]
