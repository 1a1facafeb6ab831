"""Public jit'd wrappers for the server kernels.

``backend`` selects:
  * "pallas"     — pl.pallas_call compiled for TPU (interpret=False),
  * "interpret"  — same kernel body, Python interpreter (CPU validation),
  * "jnp"        — the pure-jnp oracle from ref.py.

The default (``backend=None``) is ``$REPRO_KERNEL_BACKEND`` where it is
set, else "pallas" on a TPU and "jnp" elsewhere (``backend.py``);
the CPU paths exist for tests, and on the chip nothing gives way to them.
The numerical contract is identical, up to the TPU's bf16 matmul passes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.kernels import dequant_kl as _dk
from repro.kernels import neighbor_mean as _nm
from repro.kernels import pairwise_kl as _pk
from repro.kernels import ref as _ref
from repro.kernels import soft_ce as _sc
from repro.kernels.backend import (  # noqa: F401  (public re-exports)
    default_backend,
    default_interpret,
    resolve_interpret,
)


def operand_mesh(*args):
    """The multi-device mesh the first mesh-placed operand lives on, or
    None (single-device arrays, and tracers, which carry no placement)."""
    for a in args:
        s = getattr(a, "sharding", None)
        if isinstance(s, NamedSharding) and s.mesh.size > 1:
            return s.mesh
    return None


def replicated(kernel, mesh):
    """``kernel`` run whole on every device of ``mesh``. Mosaic kernels
    cannot be partitioned automatically, and a ``pallas_call``'s output
    carries no varying-manual-axes type, hence ``check_vma=False``."""
    return jax.shard_map(kernel, mesh=mesh, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), check_vma=False)


def _pallas(kernel, *args, **kwargs):
    """Call a Pallas kernel on operands wherever they live: arrays spread
    over the client mesh (the sharded server's repository, graph and
    targets) run it replicated on that mesh, no partitioner involved."""
    kernel = functools.partial(kernel, **kwargs)
    mesh = operand_mesh(*args)
    return kernel(*args) if mesh is None else replicated(kernel,
                                                         mesh)(*args)


# Above this many rows the square divergence rebuild streams row-block
# strips instead of one monolithic call, bounding the padded/exp'd
# intermediates each call materializes (VMEM/HBM safety at N=10k).
CHUNK_ROWS = 2048


def pairwise_kl(logp: jnp.ndarray, backend: Optional[str] = None,
                row_block: Optional[int] = None, **blocks) -> jnp.ndarray:
    """Eq.2 divergence matrix. logp (N,R,C) -> (N,N) fp32.

    Large repositories (N > CHUNK_ROWS, or any N with ``row_block`` set)
    are computed by k-strip streaming over row blocks — each block is an
    independent u×N strip, so per-call intermediates stay bounded."""
    n = logp.shape[0]
    if row_block is None and n > CHUNK_ROWS:
        row_block = CHUNK_ROWS
    if row_block is not None and row_block < n:
        strips = [pairwise_kl_pair(logp[i:i + row_block], logp,
                                   backend=backend, **blocks)
                  for i in range(0, n, row_block)]
        return jnp.concatenate(strips, axis=0)
    backend = backend or default_backend()
    if backend == "jnp":
        return _ref.pairwise_kl_ref(logp)
    return _pallas(_pk.pairwise_kl, logp,
                   interpret=(backend == "interpret"), **blocks)


# strips are hot-path (delta rounds, chunked rebuilds): jit the oracle so
# the exp/rowterm chain fuses instead of materializing eager temporaries
_pair_ref_jit = jax.jit(_ref.pairwise_kl_pair_ref)


def pairwise_kl_pair(logp_a: jnp.ndarray, logp_b: jnp.ndarray,
                     backend: Optional[str] = None, **blocks) -> jnp.ndarray:
    """Rectangular Eq.2 strip: logp_a (U,R,C), logp_b (M,R,C) -> (U,M).

    The delta-update primitive: after u uploads only the u×N and N×u
    strips of the divergence matrix change."""
    backend = backend or default_backend()
    if backend == "jnp":
        return _pair_ref_jit(logp_a, logp_b)
    return _pallas(_pk.pairwise_kl_pair, logp_a, logp_b,
                   interpret=(backend == "interpret"), **blocks)


# the oracle materializes the dense fp32 decode; jit so the dequant and
# the KL matmul still fuse into one compiled call on the jnp path
_int8_ref_jit = jax.jit(_ref.int8_pairwise_kl_ref)


def int8_pairwise_kl(q: jnp.ndarray, scale: jnp.ndarray, zp: jnp.ndarray,
                     backend: Optional[str] = None, **blocks) -> jnp.ndarray:
    """Eq.2 divergence matrix straight from the int8 wire form.

    q (N,R,C) uint8 codes, scale/zp (N,R) per-row affine params
    (``wire.Int8`` payload fields) -> (N,N) fp32. The Pallas path
    dequantizes per-tile in VMEM and never materializes the fp32
    (N,R,C) decode in HBM; the jnp path is the dense oracle."""
    backend = backend or default_backend()
    if backend == "jnp":
        return _int8_ref_jit(q, scale, zp)
    return _pallas(_dk.int8_pairwise_kl, q, scale, zp,
                   interpret=(backend == "interpret"), **blocks)


# jitted for the same reason as the square form: the double dequant +
# strip matmul fuse into one compiled call on the jnp path
_int8_pair_ref_jit = jax.jit(_ref.int8_pairwise_kl_pair_ref)


def int8_pairwise_kl_pair(qa: jnp.ndarray, sa: jnp.ndarray,
                          zpa: jnp.ndarray, qb: jnp.ndarray,
                          sb: jnp.ndarray, zpb: jnp.ndarray,
                          backend: Optional[str] = None,
                          **blocks) -> jnp.ndarray:
    """Rectangular Eq.2 strip between two int8 wire forms.

    qa (U,R,C) / qb (M,R,C) uint8 codes with per-row affine scale/zp
    (``wire.Int8`` payload fields) -> (U,M) fp32. The IVF neighbor-search
    primitive: upload-vs-candidate divergence strips computed straight
    off the stored wire form."""
    backend = backend or default_backend()
    if backend == "jnp":
        return _int8_pair_ref_jit(qa, sa, zpa, qb, sb, zpb)
    return _pallas(_dk.int8_pairwise_kl_pair, qa, sa, zpa, qb, sb, zpb,
                   interpret=(backend == "interpret"), **blocks)


def soft_ce(logits: jnp.ndarray, labels: jnp.ndarray,
            backend: Optional[str] = None, **blocks) -> jnp.ndarray:
    """Eq.1 quality scores. logits (N,R,C), labels (R,) -> (N,) fp32."""
    backend = backend or default_backend()
    if backend == "jnp":
        return _ref.soft_ce_ref(logits, labels)
    return _pallas(_sc.soft_ce, logits, labels,
                   interpret=(backend == "interpret"), **blocks)


_sparse_ref_jit = jax.jit(_ref.neighbor_mean_sparse_ref)


def neighbor_mean(neighbors: jnp.ndarray, edge_weights: jnp.ndarray,
                  probs: jnp.ndarray, backend: Optional[str] = None,
                  **blocks) -> jnp.ndarray:
    """K-sparse Eq.5 targets. neighbors (N,K) ids, edge_weights (N,K),
    probs (N,R,C) -> (N,R,C) fp32."""
    backend = backend or default_backend()
    if backend == "jnp":
        return _sparse_ref_jit(neighbors, edge_weights, probs)
    return _pallas(_nm.neighbor_mean, neighbors, edge_weights, probs,
                   interpret=(backend == "interpret"), **blocks)


def neighbor_mean_dense(w: jnp.ndarray, probs: jnp.ndarray,
                        backend: Optional[str] = None,
                        **blocks) -> jnp.ndarray:
    """Eq.5 targets of a dense graph. w (N,N), probs (N,R,C) -> (N,R,C)
    fp32."""
    backend = backend or default_backend()
    if backend == "jnp":
        return _ref.neighbor_mean_ref(w, probs)
    return _pallas(_nm.neighbor_mean_dense, w, probs,
                   interpret=(backend == "interpret"), **blocks)
