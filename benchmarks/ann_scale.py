"""Approximate-neighbor-selection benchmark: IVF index vs exact oracle.

Builds a ``NeighborIndex`` over clustered synthetic messengers at
N ∈ {10^4, 10^5, 10^6} clients and measures, per cell:

  * ``overlap``      — mean top-k selection overlap vs the exact oracle
                       (tie-safe: an IVF pick whose divergence is within
                       1e-6 of the oracle's k-th counts as a hit) on a
                       sample of freshly-updated query rows;
  * ``resident_mb``  — bytes the server holds for selection (int8 wire
                       form + top-L lists + coarse quantizer), vs the
                       dense (N,N) fp32 cache's ``dense_mb``;
  * ``upload_ms``    — one incremental ``update`` of a single fresh row
                       (assign + probe + strips + list merge);
  * ``build_s``      — bulk ingest + quantizer fit + assignment.

The dense-path contrast (one full (N,N) rebuild) is timed at the
smallest N only — it is the O(N²) cost the index exists to avoid.
Cost-model leading exponents (``ivf_search`` vs ``sqmd.build_graph``)
are embedded so the JSON records the asymptotic claim next to the
measurements. Results land in ``BENCH_ann.json``:

  PYTHONPATH=src python benchmarks/ann_scale.py            # full sweep
  PYTHONPATH=src python benchmarks/ann_scale.py --smoke    # CI: N=4096
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_N = (10_000, 100_000, 1_000_000)
SMOKE_N = (4096,)
R, C = 8, 10          # messenger dims: R·C = 80 keeps 10^6 rows tractable
K = 10                # neighbors selected per client
N_QUERY = 64          # rows sampled for the overlap measurement
N_PROTO = 128         # synthetic population: mixture of this many modes
GEN_CHUNK = 65_536
ORACLE_CHUNK = 131_072
OUT = "BENCH_ann.json"
TIE_TOL = 1e-6


def _time(fn, reps=3):
    fn()                                   # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _gen_logp(rng: np.random.Generator, protos: np.ndarray,
              count: int) -> np.ndarray:
    """Clustered messengers: prototype logits + per-client noise."""
    assign = rng.integers(0, protos.shape[0], size=count)
    logits = protos[assign] + rng.normal(scale=0.7,
                                         size=(count, R, C))
    return np.asarray(jax.nn.log_softmax(
        jnp.asarray(logits, jnp.float32), axis=-1))


def _oracle_topk_div(idx, queries: np.ndarray, n: int,
                     k: int) -> np.ndarray:
    """(q, k) exact k smallest divergences per query over ALL active
    rows (self excluded), computed off the same int8 wire form the index
    stores — chunked column strips, never an (N,N) matrix."""
    best = np.full((queries.size, k), np.inf, np.float32)
    for lo in range(0, n, ORACLE_CHUNK):
        cols = np.arange(lo, min(lo + ORACLE_CHUNK, n))
        strip = np.array(idx._strip(queries, cols))
        strip[cols[None, :] == queries[:, None]] = np.inf
        both = np.concatenate([best, strip], axis=1)
        best = np.sort(both, axis=1)[:, :k].astype(np.float32)
    return best


def bench_one(n: int, n_probe, seed: int = 0, verbose: bool = True,
              dense_contrast: bool = False) -> dict:
    from repro.core.similarity import NeighborIndex
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    protos = rng.normal(scale=2.0, size=(N_PROTO, R, C))
    idx = NeighborIndex(n, R, C, k=K, n_probe=n_probe)

    t0 = time.perf_counter()
    for lo in range(0, n, GEN_CHUNK):
        count = min(GEN_CHUNK, n - lo)
        idx.ingest_only(np.arange(lo, lo + count),
                        _gen_logp(rng, protos, count))
    idx.refresh()
    build_s = time.perf_counter() - t0

    # overlap: freshly update a sample of rows (the hot path every upload
    # takes), then grade their selected top-k against the exact oracle
    queries = np.sort(rng.choice(n, size=min(N_QUERY, n), replace=False))
    fresh = _gen_logp(rng, protos, queries.size)
    idx.update(queries, fresh)
    cand = np.ones(n, bool)
    nbrs, ndiv = idx.select(cand, K)
    oracle = _oracle_topk_div(idx, queries, n, K)
    hits = []
    for qi, row in enumerate(queries):
        got = ndiv[row][np.isfinite(ndiv[row])]
        kth = oracle[qi][min(K, np.isfinite(oracle[qi]).sum()) - 1]
        hits.append(float((got <= kth + TIE_TOL).sum()) / K)
    overlap = float(np.mean(hits))

    # per-upload latency: one fresh row through the full incremental path
    one = rng.integers(0, n, size=1)
    lp_one = _gen_logp(rng, protos, 1)
    upload_s = _time(lambda: idx.update(one, lp_one))

    row = {
        "selection": "ivf", "n_clients": n, "ref_size": R, "n_classes": C,
        "n_probe": idx._effective_probe(), "n_centroids": idx.n_centroids,
        "k": K, "overlap": round(overlap, 4),
        "resident_mb": round(idx.bytes_resident() / 2**20, 2),
        "dense_mb": round(4.0 * n * n / 2**20, 2),
        "build_s": round(build_s, 3),
        "upload_ms": round(upload_s * 1e3, 3),
    }
    if dense_contrast:
        logp = jnp.asarray(idx._recon_logp(np.arange(n)))
        row["dense_rebuild_s"] = round(_time(
            lambda: jax.block_until_ready(
                ops.pairwise_kl(logp, backend="jnp")), reps=1), 3)
    if verbose:
        print(f"N={n:>9,}  overlap={overlap:.3f}  "
              f"resident={row['resident_mb']:.1f}MB "
              f"(dense {row['dense_mb']:.0f}MB)  "
              f"upload={row['upload_ms']:.1f}ms  build={build_s:.1f}s")
    return row


def _exponents() -> dict:
    from repro.analysis.cost import model
    rep = model.scaling_report()
    return {
        "ivf_search": round(rep["ivf_search"]["temp_bytes"]["leading"], 3),
        "dense_rebuild": round(
            rep["sqmd.build_graph"]["temp_bytes"]["leading"], 3),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, action="append",
                    help="population size(s); default the full sweep")
    ap.add_argument("--n-probe", type=int, default=None,
                    help="clusters probed per query (default isqrt(ncent))")
    ap.add_argument("--smoke", action="store_true",
                    help=f"CI mode: N={SMOKE_N[0]} only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help=f"output path (default <repo>/{OUT})")
    args = ap.parse_args(argv)

    sizes = tuple(args.n) if args.n else (SMOKE_N if args.smoke
                                          else DEFAULT_N)
    rows = [bench_one(n, args.n_probe, seed=args.seed,
                      dense_contrast=(n == min(sizes)))
            for n in sizes]
    exponents = _exponents()
    big = [r for r in rows if r["n_clients"] >= 100_000]
    acceptance = {
        "overlap_ok": all(r["overlap"] >= 0.9 for r in rows),
        "resident_under_1gb": (all(r["resident_mb"] < 1024.0 for r in big)
                               if big else None),
        "ivf_exponent_sublinear": exponents["ivf_search"] < 1.5,
    }
    out = {"rows": rows, "exponents": exponents, **acceptance}
    path = Path(args.out) if args.out else \
        Path(__file__).resolve().parent.parent / OUT
    path.write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {path}  overlap_ok={acceptance['overlap_ok']} "
          f"ivf_exp={exponents['ivf_search']} "
          f"dense_exp={exponents['dense_rebuild']}")
    return 0 if acceptance["overlap_ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
