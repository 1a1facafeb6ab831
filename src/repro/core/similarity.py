"""Inter-model similarity (paper Def. 4, Eq. 2).

d_nm = (1/R) Σ_j KL(s^n_j || s^m_j) — asymmetric; similarity c_nm = 1/d_nm.
The (N,N) divergence matrix is the server's O(N²RC) hot spot → Pallas
kernel (kernels/pairwise_kl.py).

``update_divergence_cache`` is the incremental path: after u fresh uploads
only row-strip D[u,:] and column-strip D[:,u] change, so the server pays
O(u·N·R·C) per trigger instead of the O(N²·R·C) full rebuild. Rows are
padded up to power-of-two buckets (repeating the last row — duplicate
scatters write identical values) so the strip kernel compiles once per
bucket, not once per distinct upload count.

``NeighborIndex`` is the sub-quadratic path for million-client graphs:
no (N,N) matrix at all. The repository stays in int8 wire form, clients
are clustered IVF-style under a k-means coarse quantizer, and each upload
pays exact rectangular KL strips only against its probed clusters while
per-client top-L neighbor lists are maintained incrementally.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.obs import host_read, span

EPS = 1e-8


def divergence_matrix(messengers_logp: jnp.ndarray,
                      mesh=None) -> jnp.ndarray:
    """(N,R,C) log-messengers -> (N,N) fp32, D[n,m] = mean_j KL(n || m).

    With a client ``mesh`` (repro.sharding.make_client_mesh) the rebuild
    shards ROW-WISE: each device computes its own (N/n_dev, N) strip with
    the rectangular strip kernel against the replicated repository — the
    same per-row math as the single-device path with no cross-device
    reductions (XLA's per-shard matmul tiling can still differ at the
    fp32 ULP level; parity tests assert <= 1e-6). Repositories that don't
    divide the mesh are padded with a repeated last row and sliced
    back."""
    if mesh is not None and _mesh_devices(mesh) > 1:
        return _divergence_sharded(messengers_logp, mesh)
    return ops.pairwise_kl(messengers_logp)


def _mesh_devices(mesh) -> int:
    from repro.sharding import CLIENT_AXIS
    return int(mesh.shape.get(CLIENT_AXIS, 1))


# Below this many rows per shard the jnp strip flips to the pre-transposed
# layout: narrow per-shard GEMMs (M = N/n_dev) lose the transposed-B form's
# cache locality, and re-deriving B^T inside every shard repeats an O(N·R·C)
# relayout n_dev times. Hoisting one (RC, N) transpose out of the shard_map
# removed the 8-device regression (BENCH_shard: 788ms -> 589ms at N=4096)
# while the wide-shard (<= 2 devices at N=4096) nt-form GEMM stays faster
# untransposed, so the layout is picked per trace from the static shapes.
_PRETRANSPOSE_ROWS = 1024


@functools.lru_cache(maxsize=None)
def _sharded_strip_fn(mesh, path: str):
    """shard_map'd row-strip rebuild, cached per (mesh, resolved kernel
    ``path``) so each repository shape compiles once. Both layouts keep
    the replicated operand un-reduced per shard — zero collectives (the
    PR 6 HLO pin)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import CLIENT_AXIS

    n_dev = int(mesh.shape.get(CLIENT_AXIS, 1))

    def strips(block, full):
        # block: this device's rows; full: the whole repository
        # (replicated) — the PR 3 rectangular strip kernel per shard
        return ops.pairwise_kl_pair(block, full, backend=path)

    def strips_pre_t(la_blk, lt_full):
        # la_blk (rows, R*C) this device's flattened rows; lt_full
        # (R*C, N) the repository pre-transposed ONCE outside the
        # shard_map — per-shard work is one exp + one nn-form GEMM
        pa = jnp.exp(la_blk)
        rowterm = jnp.sum(pa * la_blk, axis=-1)
        return rowterm[:, None] - pa @ lt_full

    def rebuild(lp_padded, lp_full):
        rows = lp_padded.shape[0] // n_dev
        if path != "jnp" or rows >= _PRETRANSPOSE_ROWS:
            # a pallas_call's output carries no varying-manual-axes type,
            # so the kernel branch cannot be vma-checked
            return jax.shard_map(
                strips, mesh=mesh,
                in_specs=(P(CLIENT_AXIS, None, None), P(None, None, None)),
                out_specs=P(CLIENT_AXIS, None),
                check_vma=False)(lp_padded, lp_full)
        n, r, c = lp_full.shape
        la = lp_padded.astype(jnp.float32).reshape(lp_padded.shape[0],
                                                   r * c)
        lt = lp_full.astype(jnp.float32).reshape(n, r * c).T
        return jax.shard_map(
            strips_pre_t, mesh=mesh,
            in_specs=(P(CLIENT_AXIS, None), P(None, None)),
            out_specs=P(CLIENT_AXIS, None))(la, lt) / r

    return jax.jit(rebuild)


def _divergence_sharded(messengers_logp: jnp.ndarray, mesh) -> jnp.ndarray:
    n = messengers_logp.shape[0]
    n_dev = _mesh_devices(mesh)
    pad = (-n) % n_dev
    lp = messengers_logp
    if pad:
        lp = jnp.concatenate(
            [lp, jnp.broadcast_to(lp[-1:], (pad,) + lp.shape[1:])])
    d = _sharded_strip_fn(mesh, ops.default_backend())(lp, messengers_logp)
    return d[:n] if pad else d


def _bucket_rows(rows: np.ndarray) -> np.ndarray:
    """Pad the updated-row index set up to the next power of two by
    repeating the last index — a no-op for the scatter, a cache hit for
    the jit'd strip kernel."""
    u = len(rows)
    size = 1 << (u - 1).bit_length() if u > 1 else 1
    return np.concatenate([rows, np.full(size - u, rows[-1], rows.dtype)])


@jax.jit
def _scatter_strips(cache: jnp.ndarray, rows: jnp.ndarray,
                    row_strip: jnp.ndarray,
                    col_strip: jnp.ndarray) -> jnp.ndarray:
    cache = cache.astype(jnp.float32)
    cache = cache.at[rows, :].set(row_strip)
    return cache.at[:, rows].set(col_strip)


@functools.partial(jax.jit, static_argnames=("r",))
def _delta_update(cache: jnp.ndarray, lp: jnp.ndarray, rows: jnp.ndarray,
                  r: int) -> jnp.ndarray:
    """Fused jnp delta path: strips + scatter in one compiled call (the
    eager composition pays several O(N²) temporaries; fused it is one
    O(u·N·R·C) matmul pair plus one cache copy)."""
    fresh_l = lp[rows]
    fresh_p = jnp.exp(fresh_l)
    p = jnp.exp(lp)
    row_strip = (jnp.sum(fresh_p * fresh_l, axis=-1)[:, None]
                 - fresh_p @ lp.T) / r                      # (u, N)
    col_strip = (jnp.sum(p * lp, axis=-1)[:, None]
                 - p @ fresh_l.T) / r                       # (N, u)
    return _scatter_strips(cache, rows, row_strip, col_strip)


def update_divergence_cache(cache: jnp.ndarray, messengers_logp: jnp.ndarray,
                            uploaded) -> jnp.ndarray:
    """Scatter the divergence strips of freshly-uploaded rows into the
    cached (N,N) matrix.

    ``uploaded`` is a boolean (N,) mask of every row whose repository
    entry changed since ``cache`` was built. Rows outside it are assumed
    untouched — the ServerBus accumulates the mask across deliveries
    between trigger fires. Returns the updated (N,N) fp32 matrix, equal
    (to fp32 tolerance) to a full rebuild."""
    uploaded = np.asarray(uploaded)
    if uploaded.dtype != bool:
        # a 0/1 integer array is ambiguous (mask or index list?) — demand
        # the mask form rather than silently updating the wrong rows
        raise TypeError(f"uploaded must be a boolean mask, got dtype "
                        f"{uploaded.dtype}")
    rows = np.nonzero(uploaded)[0]
    if rows.size == 0:
        return cache
    if rows.size >= messengers_logp.shape[0]:
        return divergence_matrix(messengers_logp)
    bucket = _bucket_rows(rows)
    with span("repro.div_update", rows=rows.size, bucket=bucket.size):
        rows = jnp.asarray(bucket)
        if ops.default_backend() == "jnp":
            n, r, c = messengers_logp.shape
            lp = messengers_logp.astype(jnp.float32).reshape(n, r * c)
            return _delta_update(cache, lp, rows, r)
        fresh = messengers_logp[rows]
        row_strip = ops.pairwise_kl_pair(fresh, messengers_logp)  # (u, N)
        col_strip = ops.pairwise_kl_pair(messengers_logp, fresh)  # (N, u)
        return _scatter_strips(cache, rows, row_strip, col_strip)


@jax.jit
def similarity_matrix(divergence: jnp.ndarray) -> jnp.ndarray:
    """c_nm = 1 / d_nm (paper Def. 4). Diagonal forced to 0 so a client is
    never its own neighbor; numerical floor keeps identical twins finite.

    Jitted: one fused pass over the (N,N) matrix — at N=10k the eager
    chain (maximum, reciprocal, eye, multiply) costs several 400MB
    temporaries."""
    c = 1.0 / jnp.maximum(divergence, EPS)
    n = c.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return c * (i != j).astype(c.dtype)


# ---------------------------------------------------------------------------
# Approximate neighbor selection: IVF-clustered top-K over the int8 wire form
# ---------------------------------------------------------------------------

_KMEANS_SAMPLE = 4096   # k-means fits on a bounded sample of active rows
_KMEANS_ITERS = 8
_ASSIGN_CHUNK = 8192    # bulk-reassign strips are bounded to (chunk, ncent)
_REFIT_GROWTH = 4       # refit the quantizer when |active| grows this factor
_PROB_FLOOR = 1e-8      # centroid probability floor before the log transform


@jax.jit
def _encode_wire_rows(logp: jnp.ndarray):
    """(u,R,C) fp32 log-probs -> (codes uint8, scale fp32, lse fp32).

    Mirrors ``wire.Int8.encode`` bit-for-bit (quantize against the
    bf16-ROUNDED affine params), then precomputes lse = logsumexp(q·scale)
    so reconstruction is logp = q·scale − lse — the per-row zero-point is
    an additive shift the softmax renorm cancels, so it is never stored."""
    x = jnp.asarray(logp, jnp.float32)
    lo = jnp.min(x, axis=-1)
    hi = jnp.max(x, axis=-1)
    scale = jnp.maximum((hi - lo) / 255.0, 1e-8).astype(jnp.bfloat16)
    zp = lo.astype(jnp.bfloat16)
    q = jnp.clip(jnp.round((x - zp.astype(jnp.float32)[..., None])
                           / scale.astype(jnp.float32)[..., None]),
                 0.0, 255.0).astype(jnp.uint8)
    scale_f = scale.astype(jnp.float32)
    lse = jax.nn.logsumexp(q.astype(jnp.float32) * scale_f[..., None],
                           axis=-1)
    return q, scale_f, lse


class NeighborIndex:
    """IVF-clustered incremental top-K neighbor index over the int8 wire
    form — the server never materializes an (N,N) divergence matrix.

    State per client: uint8 codes (R,C) + fp32 scale/lse row stats (the
    wire form, ~R·C bytes) and a top-L neighbor list (L = list_margin·k)
    of (id, exact divergence) pairs — O(N·(R·C + L)) bytes total, versus
    the dense cache's O(N²).

    A k-means coarse quantizer over the dequantized messengers assigns
    every client to one of ~sqrt(N) clusters. On upload, the fresh rows
    are assigned, their ``n_probe`` nearest clusters are probed, and
    exact rectangular KL strips (``ops.int8_pairwise_kl_pair``) are
    computed only against the probed clusters' members — forward strips
    rebuild the uploaders' own lists, reverse strips merge the uploaders
    into every candidate's list. A merge that RAISES a stored divergence
    (or a neighbor deactivation) can silently invalidate a list's top-L
    property, so such rows are marked degraded and rebuilt exactly from a
    fresh strip in the same call; with ``n_probe >= n_centroids``
    (probe-all) every list is therefore EXACTLY the top-L over active
    clients at all times — the property-tested oracle contract. Partial
    probing trades that guarantee for sub-quadratic cost; quality is
    measured as top-k overlap vs the exact oracle (benchmarks/
    ann_scale.py gates >= 0.9).
    """

    def __init__(self, capacity: int, ref_size: int, n_classes: int,
                 k: int, n_probe: Optional[int] = None,
                 n_centroids: Optional[int] = None,
                 list_margin: int = 2, seed: int = 0):
        if capacity < 1 or ref_size < 1 or n_classes < 2:
            raise ValueError(f"bad index dims: capacity={capacity}, "
                             f"ref_size={ref_size}, n_classes={n_classes}")
        if k < 1 or list_margin < 1:
            raise ValueError(f"bad list config: k={k}, "
                             f"list_margin={list_margin}")
        self.capacity = capacity
        self.r = ref_size
        self.c = n_classes
        self.k = k
        self.list_len = list_margin * k
        self.n_probe = n_probe          # None -> derived from ncent at fit
        self._n_centroids = n_centroids  # None -> isqrt(|active|) at fit
        self.seed = seed
        n, L = capacity, self.list_len
        self._codes = np.zeros((n, ref_size, n_classes), np.uint8)
        self._scale = np.zeros((n, ref_size), np.float32)
        self._lse = np.zeros((n, ref_size), np.float32)
        self._active = np.zeros(n, bool)
        self._assign = np.full(n, -1, np.int32)
        self._list_ids = np.full((n, L), -1, np.int32)
        self._list_div = np.full((n, L), np.inf, np.float32)
        self._searched = np.zeros(n, bool)   # rows with a built list
        self._centroids = None           # (ncent, R, C) fp32 logp
        self._fit_active = 0             # |active| at the last fit
        self._fit_epoch = 0

    # -- core accessors ----------------------------------------------------
    def active_rows(self) -> np.ndarray:
        """(capacity,) bool — rows currently in the index (a copy)."""
        return self._active.copy()

    @property
    def n_centroids(self) -> int:
        return 0 if self._centroids is None else self._centroids.shape[0]

    def bytes_resident(self) -> int:
        """Server-side bytes held by the index (wire form + lists +
        quantizer) — the quantity the dense (N,N) cache made quadratic."""
        total = (self._codes.nbytes + self._scale.nbytes + self._lse.nbytes
                 + self._active.nbytes + self._assign.nbytes
                 + self._list_ids.nbytes + self._list_div.nbytes)
        if self._centroids is not None:
            total += self._centroids.nbytes
        return total

    def _recon_logp(self, rows: np.ndarray) -> np.ndarray:
        """Reconstruct (u,R,C) fp32 log-probs from the stored wire form."""
        return (self._codes[rows].astype(np.float32)
                * self._scale[rows][..., None]
                - self._lse[rows][..., None])

    # -- coarse quantizer --------------------------------------------------
    def refresh(self) -> None:
        """(Re)fit the k-means coarse quantizer on a sample of active rows
        and bulk-reassign every active row. Neighbor lists are untouched:
        they hold exact pair divergences, which a re-clustering does not
        change."""
        act = np.nonzero(self._active)[0]
        if act.size == 0:
            self._centroids = None
            self._fit_active = 0
            return
        ncent = self._n_centroids or max(1, math.isqrt(act.size))
        ncent = min(ncent, act.size)
        rng = np.random.default_rng([self.seed, self._fit_epoch])
        self._fit_epoch += 1
        samp = rng.choice(act, size=min(_KMEANS_SAMPLE, act.size),
                          replace=False)
        x = np.exp(self._recon_logp(samp)).reshape(samp.size, -1)
        cent = x[rng.choice(x.shape[0], size=ncent, replace=False)]
        x2 = (x * x).sum(-1)
        for _ in range(_KMEANS_ITERS):
            d = x2[:, None] + (cent * cent).sum(-1)[None, :] - 2.0 * (x @ cent.T)
            a = d.argmin(1)
            sums = np.zeros_like(cent)
            np.add.at(sums, a, x)
            counts = np.bincount(a, minlength=ncent).astype(np.float32)
            # empty clusters keep their old centroid rather than collapsing
            cent = np.where(counts[:, None] > 0,
                            sums / np.maximum(counts, 1.0)[:, None], cent)
        cp = np.clip(cent.reshape(ncent, self.r, self.c), _PROB_FLOOR, None)
        cp /= cp.sum(-1, keepdims=True)
        self._centroids = np.log(cp).astype(np.float32)
        self._fit_active = act.size
        for i in range(0, act.size, _ASSIGN_CHUNK):
            chunk = act[i:i + _ASSIGN_CHUNK]
            self._assign[chunk] = self._centroid_div(chunk).argmin(1)

    def _maybe_refit(self) -> None:
        n_act = int(self._active.sum())
        if (self._centroids is None
                or n_act >= _REFIT_GROWTH * max(self._fit_active, 1)):
            self.refresh()

    def _centroid_div(self, rows: np.ndarray) -> np.ndarray:
        """(u, ncent) exact Eq.2 divergence row -> centroid (the
        assignment/probing metric — same metric as the lists hold)."""
        return host_read(ops.pairwise_kl_pair(
            jnp.asarray(self._recon_logp(rows)),
            jnp.asarray(self._centroids)),
            "ivf.centroid_div")

    def _effective_probe(self) -> int:
        ncent = self.n_centroids
        probe = self.n_probe if self.n_probe is not None \
            else max(1, math.isqrt(ncent))
        return min(probe, ncent)

    # -- strip search ------------------------------------------------------
    def _strip(self, rows_a: np.ndarray,
               rows_b: np.ndarray) -> np.ndarray:
        """Exact (|a|,|b|) KL strip straight off the stored wire form."""
        zp_a = np.zeros_like(self._scale[rows_a])
        zp_b = np.zeros_like(self._scale[rows_b])
        return host_read(ops.int8_pairwise_kl_pair(
            jnp.asarray(self._codes[rows_a]),
            jnp.asarray(self._scale[rows_a]), jnp.asarray(zp_a),
            jnp.asarray(self._codes[rows_b]),
            jnp.asarray(self._scale[rows_b]), jnp.asarray(zp_b)),
            "ivf.strip")

    def _search(self, rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """rows (u,) -> (candidates (m,), fwd strip (u,m)).

        Candidates are the active members of the union of each row's
        ``n_probe`` nearest clusters; the strip is exact."""
        d_cent = self._centroid_div(rows)
        self._assign[rows] = d_cent.argmin(1)
        probe = np.argsort(d_cent, axis=1)[:, :self._effective_probe()]
        cand = np.nonzero(self._active
                          & np.isin(self._assign, np.unique(probe)))[0]
        if cand.size == 0:
            return cand, np.zeros((rows.size, 0), np.float32)
        return cand, self._strip(rows, cand)

    def _set_lists(self, rows: np.ndarray, cand: np.ndarray,
                   strip: np.ndarray) -> None:
        """Overwrite rows' lists with the top-L of their strip columns
        (self-edges masked)."""
        L = self.list_len
        div = strip.copy()
        div[cand[None, :] == rows[:, None]] = np.inf
        take = min(L, div.shape[1])
        order = np.argsort(div, axis=1, kind="stable")[:, :take]
        top_div = np.take_along_axis(div, order, axis=1)
        top_ids = cand[order].astype(np.int32)
        if take < L:
            pad = L - take
            top_div = np.pad(top_div, ((0, 0), (0, pad)),
                             constant_values=np.inf)
            top_ids = np.pad(top_ids, ((0, 0), (0, pad)),
                             constant_values=-1)
        top_ids = np.where(np.isfinite(top_div), top_ids, -1)
        self._list_ids[rows] = top_ids
        self._list_div[rows] = top_div.astype(np.float32)
        self._searched[rows] = True

    def _merge_rev(self, rows: np.ndarray, targets: np.ndarray,
                   rev: np.ndarray) -> np.ndarray:
        """Merge uploaded ``rows`` into ``targets``' lists using the
        exact reverse strip ``rev`` (|targets|, u). In-place updates that
        RAISE a stored divergence break the top-L property — those
        targets are returned for exact rebuild."""
        L = self.list_len
        ids_t = self._list_ids[targets]
        div_t = self._list_div[targets]
        match = ids_t[:, :, None] == rows[None, None, :]   # (m, L, u)
        matched = match.any(axis=2)
        fresh = np.where(matched,
                         (match * rev[:, None, :]).sum(2), div_t)
        degraded = (fresh > div_t * (1.0 + 1e-6) + 1e-12).any(axis=1)
        div_t = fresh.astype(np.float32)
        # rows already updated in place must not be inserted again; a
        # target never lists itself
        rev_m = np.where(match.any(axis=1), np.inf, rev)
        rev_m[targets[:, None] == rows[None, :]] = np.inf
        comb_div = np.concatenate([div_t, rev_m.astype(np.float32)], axis=1)
        comb_ids = np.concatenate(
            [ids_t, np.broadcast_to(rows[None, :], rev_m.shape)
             .astype(np.int32)], axis=1)
        order = np.argsort(comb_div, axis=1, kind="stable")[:, :L]
        new_div = np.take_along_axis(comb_div, order, axis=1)
        new_ids = np.take_along_axis(comb_ids, order, axis=1)
        new_ids = np.where(np.isfinite(new_div), new_ids, -1)
        self._list_ids[targets] = new_ids
        self._list_div[targets] = new_div
        return targets[degraded]

    # -- public mutation API ----------------------------------------------
    def ingest_only(self, rows, logp) -> None:
        """Store rows' wire forms and activate them WITHOUT maintaining
        any neighbor list — the bulk-build path (benchmarks, snapshot
        restore). Follow with ``refresh()``; lists materialize lazily as
        rows pass through ``update``."""
        rows = np.asarray(rows, np.int64)
        q, s, l = _encode_wire_rows(jnp.asarray(logp))
        self._codes[rows] = host_read(q, "ivf.ingest")
        self._scale[rows] = host_read(s, "ivf.ingest")
        self._lse[rows] = host_read(l, "ivf.ingest")
        self._active[rows] = True

    def update(self, rows, logp) -> int:
        """Ingest freshly-uploaded rows and repair the neighbor lists:
        rebuild the uploaders' own lists from forward strips, merge them
        into every candidate's list from reverse strips, and exactly
        rebuild any list the merge degraded. Returns the number of
        degraded rows rebuilt (diagnostic)."""
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return 0
        # dedup (last write wins) and keep the payload aligned with the
        # sorted unique ids
        rows_u, first = np.unique(rows[::-1], return_index=True)
        logp = np.asarray(logp)[::-1][first]
        rows = rows_u
        if rows.max() >= self.capacity or rows.min() < 0:
            raise ValueError(f"row ids out of range [0, {self.capacity}): "
                             f"{rows.min()}..{rows.max()}")
        self.ingest_only(rows, logp)
        self._maybe_refit()
        cand, fwd = self._search(rows)
        self._set_lists(rows, cand, fwd)
        targets = cand[~np.isin(cand, rows)]
        if targets.size == 0:
            return 0
        rev = self._strip(targets, rows)
        degraded = self._merge_rev(rows, targets, rev)
        for i in range(0, degraded.size, _ASSIGN_CHUNK):
            chunk = degraded[i:i + _ASSIGN_CHUNK]
            c, f = self._search(chunk)
            self._set_lists(chunk, c, f)
        return int(degraded.size)

    def sync_active(self, active) -> None:
        """Fold the server's (capacity,) active mask into the index.
        Deactivated clients are dropped from the population and every
        list that referenced one is rebuilt exactly (a shrunk list may
        have lost top-L members to the filter)."""
        active = np.asarray(active, bool)
        if active.shape != (self.capacity,):
            raise ValueError(f"active mask shape {active.shape} != "
                             f"({self.capacity},)")
        dropped = np.nonzero(self._active & ~active)[0]
        self._active &= active
        if dropped.size == 0 or self._centroids is None:
            return
        hit = np.isin(self._list_ids, dropped).any(axis=1) & self._active
        stale = np.nonzero(hit)[0]
        for i in range(0, stale.size, _ASSIGN_CHUNK):
            chunk = stale[i:i + _ASSIGN_CHUNK]
            c, f = self._search(chunk)
            self._set_lists(chunk, c, f)

    # -- selection ---------------------------------------------------------
    def select(self, cand_mask, k: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Per-client top-k neighbors among the candidate pool.

        cand_mask (capacity,) bool — the quality pool Q. Returns
        (neighbors (capacity,k) int32 with -1 padding, divergence
        (capacity,k) fp32 with +inf padding). A client never selects
        itself, a ghost (never-ingested), an inactive client, or a
        non-candidate."""
        k = self.k if k is None else k
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        cand_mask = np.asarray(cand_mask, bool)
        if cand_mask.shape != (self.capacity,):
            raise ValueError(f"candidate mask shape {cand_mask.shape} != "
                             f"({self.capacity},)")
        ids = self._list_ids
        safe = np.maximum(ids, 0)
        valid = ((ids >= 0) & self._active[safe] & cand_mask[safe]
                 & (ids != np.arange(self.capacity)[:, None]))
        div = np.where(valid, self._list_div, np.inf)
        k = min(k, self.list_len)
        order = np.argsort(div, axis=1, kind="stable")[:, :k]
        top_div = np.take_along_axis(div, order, axis=1)
        top_ids = np.take_along_axis(ids, order, axis=1)
        top_ids = np.where(np.isfinite(top_div), top_ids, -1)
        top_ids = top_ids.astype(np.int32)
        top_div = top_div.astype(np.float32)
        # repair pass: a top-L list filtered by a SMALL candidate pool can
        # retain fewer than k entries even though better candidates exist
        # outside the list (the list is top-L over ALL active clients, the
        # pool changes every round). Those rows get an exact strip search
        # against the pool — entries that DID survive the filter are
        # already the true pool-best, so only deficient rows pay. Rows
        # that never went through a list build (ingest_only, no update)
        # are left empty rather than escalated to a dense pool search.
        pool = np.nonzero(cand_mask & self._active)[0]
        if pool.size:
            reach = pool.size - (cand_mask & self._active)[
                np.arange(self.capacity)].astype(np.int64)
            have = (top_ids >= 0).sum(axis=1)
            deficient = np.nonzero(
                self._active & self._searched
                & (have < np.minimum(k, reach)))[0]
            for i in range(0, deficient.size, _ASSIGN_CHUNK):
                rows = deficient[i:i + _ASSIGN_CHUNK]
                strip = np.array(self._strip(rows, pool))
                strip[pool[None, :] == rows[:, None]] = np.inf
                take = min(k, strip.shape[1])
                o = np.argsort(strip, axis=1, kind="stable")[:, :take]
                d = np.take_along_axis(strip, o, axis=1)
                sel = np.where(np.isfinite(d), pool[o], -1)
                top_ids[rows] = -1
                top_div[rows] = np.inf
                top_ids[rows, :take] = sel
                top_div[rows, :take] = d
        return top_ids, top_div
