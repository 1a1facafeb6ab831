"""Server-scale benchmark: incremental vs full collaboration-graph cost.

Measures one SQMD server graph update at N ∈ {256, 1k, 4k, 10k} clients:

  * full    — ``build_graph``: rebuild the whole O(N²·R·C) divergence
              matrix (the pre-delta behaviour; N > 2048 streams row-block
              strips via the chunked driver, so 10k never materializes
              oversized intermediates in one call);
  * delta   — ``build_graph_delta`` with ``--uploads`` fresh rows: scatter
              u×N / N×u strips into the cached matrix, O(u·N·R·C).

Every run asserts the delta-updated matrix equals the full rebuild (fp32
tolerance) before timing. Results land in ``BENCH_server_scale.json``
(repo root by default):

  PYTHONPATH=src python benchmarks/server_scale.py              # all N
  PYTHONPATH=src python benchmarks/server_scale.py --n 4096     # one N
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_N = (256, 1024, 4096, 10240)
OUT = "BENCH_server_scale.json"


def _time(fn, reps=None):
    """Min-of-reps wall time: the minimum is the least noisy estimator of
    compute cost on a shared/2-core box (allocator + scheduler noise only
    ever adds time)."""
    jax.block_until_ready(fn())          # warmup / compile
    if reps is None:
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        once = time.perf_counter() - t0
        reps = max(3, min(10, int(3.0 / max(once, 1e-4))))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def bench_one(n: int, r: int, c: int, uploads: int, backend: str,
              seed: int = 0, verbose: bool = True) -> dict:
    from repro.core import init_server, upload_messengers
    from repro.core.policies import as_policy
    from repro.core.protocols import sqmd

    key = jax.random.key(seed)
    logp = jax.nn.log_softmax(
        jax.random.normal(key, (n, r, c), jnp.float32) * 2.0, -1)
    labels = jax.random.randint(jax.random.key(seed + 1), (r,), 0, c)
    state = upload_messengers(init_server(n, r, c), logp,
                              jnp.ones((n,), bool))
    pol = as_policy(sqmd(q=min(64, n), k=min(8, n - 1)))
    quality = pol.grade(state, labels, backend=backend)

    # one full rebuild seeds the cache (and is the timing baseline)
    full_graph = pol.build_graph(state, quality, backend=backend)
    state = pol.update_state(state, quality, full_graph)

    # u freshly-uploaded rows: new messengers merged into the repository
    mask = np.zeros(n, bool)
    mask[np.random.default_rng(seed).choice(n, uploads, replace=False)] = True
    fresh = jax.nn.log_softmax(
        jax.random.normal(jax.random.key(seed + 2), (n, r, c)) * 2.0, -1)
    state = upload_messengers(state, fresh, jnp.asarray(mask))

    # correctness gate before any timing: delta scatter == full rebuild
    delta_graph = pol.build_graph_delta(state, quality, mask,
                                        backend=backend)
    oracle = pol.build_graph(state, quality, backend=backend)
    err = float(jnp.max(jnp.abs(delta_graph.divergence - oracle.divergence)))
    scale = float(jnp.max(jnp.abs(oracle.divergence)))
    if not err <= 1e-4 * max(scale, 1.0):
        raise AssertionError(f"delta path diverged from oracle: "
                             f"max|err|={err:.3e} (N={n})")

    from repro.core.similarity import (divergence_matrix,
                                       update_divergence_cache)

    # (a) the divergence matrix itself: full O(N²·R·C) rebuild vs the
    #     O(u·N·R·C) strip-scatter — the delta path vs full rebuild
    t_full = _time(lambda: divergence_matrix(state.repo_logp,
                                             backend=backend))
    t_delta = _time(lambda: update_divergence_cache(
        state.div_cache, state.repo_logp, mask, backend=backend))
    # (b) the whole graph build (divergence + Def.4/5 pool selection) —
    #     what one server trigger actually costs end to end
    t_full_g = _time(lambda: pol.build_graph(state, quality,
                                             backend=backend).edge_weights)
    t_delta_g = _time(lambda: pol.build_graph_delta(
        state, quality, mask, backend=backend).edge_weights)
    row = {
        "n_clients": n, "ref_size": r, "n_classes": c, "uploads": uploads,
        "backend": backend,
        "full_rebuild_s": t_full, "delta_update_s": t_delta,
        "delta_speedup": t_full / t_delta,
        "graph_full_s": t_full_g, "graph_delta_s": t_delta_g,
        "graph_delta_speedup": t_full_g / t_delta_g,
        "full_rounds_per_s": 1.0 / t_full_g,
        "delta_rounds_per_s": 1.0 / t_delta_g,
        "max_abs_err_vs_oracle": err,
    }
    if verbose:
        print(f"  N={n:6d} u={uploads}: div {t_full*1e3:8.1f}ms -> "
              f"{t_delta*1e3:7.1f}ms ({row['delta_speedup']:5.1f}x)   "
              f"graph {t_full_g*1e3:8.1f}ms -> {t_delta_g*1e3:7.1f}ms "
              f"({row['graph_delta_speedup']:4.1f}x, "
              f"{row['delta_rounds_per_s']:7.2f} rounds/s)", flush=True)
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, nargs="*",
                    help=f"client counts (default {DEFAULT_N})")
    ap.add_argument("--ref-size", type=int, default=240,
                    help="R — the paper's SC reference-set size "
                         "(sc_like default)")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--uploads", type=int, default=1,
                    help="fresh rows per trigger (the delta size u)")
    ap.add_argument("--backend", choices=("pallas", "interpret", "jnp"),
                    default="jnp")
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args()

    sizes = tuple(args.n) if args.n else DEFAULT_N
    print(f"== Server graph scaling: full O(N^2 R C) rebuild vs "
          f"O(u N R C) delta (backend={args.backend}) ==", flush=True)
    rows = []
    for n in sizes:
        rows.append(bench_one(n, args.ref_size, args.classes,
                              min(args.uploads, n), args.backend))
        jax.clear_caches()
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=2)
    worst = min(r["delta_speedup"] for r in rows)
    print(f"server_scale,{rows[-1]['delta_update_s']*1e6:.0f},"
          f"min_speedup={worst:.1f}x -> {args.out}")


if __name__ == "__main__":
    main()
