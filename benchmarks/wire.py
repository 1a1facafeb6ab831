"""Messenger wire-format benchmark: bytes vs fidelity per codec.

For each ``R x C`` messenger shape and each registered codec this
measures what the bandwidth story actually costs:

  * bytes/messenger (and the ratio vs the fp32 ``dense32`` oracle),
  * round-trip KL error of decode(encode(S)) per reference sample,
  * top-K neighbor-selection overlap: the SQMD graph built from the
    decoded repository vs the graph the dense oracle builds — the
    downstream metric that decides whether a codec is safe to train on,
  * for ``int8``: the fused dequant->KL kernel vs decode-then-KL.

Messengers are drawn with latent cluster structure (group prototypes +
per-client noise), mirroring the paper's sub-populations — so neighbor
overlap measures codec fidelity, not tie-breaking among
indistinguishable clients. Results land in ``BENCH_wire.json``:

  PYTHONPATH=src python benchmarks/wire.py            # full sweep
  PYTHONPATH=src python benchmarks/wire.py --smoke    # tiny CI shapes
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

OUT = "BENCH_wire.json"
# (R, C) sweeps: reference-set sizes around the paper's (120-480) and
# label spaces from Speech-Commands-scale (35) upward. NOTE: int8's
# fixed 4-byte/row scale+zero-point overhead needs C >= 32 to clear the
# 3.5x acceptance ratio — smaller label spaces (the 2/3-class clinical
# sets) compress proportionally less.
SHAPES = [(120, 32), (240, 35), (480, 35), (240, 64)]
SMOKE_SHAPES = [(24, 32)]
CODECS = ("dense32", "dense16", "int8", "topk", "topk:4")


def _clustered_messengers(key, n: int, r: int, c: int,
                          groups: int = 8) -> jnp.ndarray:
    k1, k2 = jax.random.split(key)
    proto = jax.random.normal(k1, (groups, r, c)) * 3.0
    noise = jax.random.normal(k2, (n, r, c)) * 0.5
    logits = proto[jnp.arange(n) % groups] + noise
    return jax.nn.log_softmax(logits, -1)


def _time(fn, reps: int = 5) -> float:
    jax.block_until_ready(fn())          # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _graph_neighbors(div, n: int, q: int, k: int):
    from repro.core.graph import select_neighbors
    cand = jnp.ones((n,), bool)
    if q < n:
        # the quality pool of a real round; rank by random-but-fixed
        # grades so every codec sees the same pool
        cand = cand.at[jnp.arange(n) >= q].set(False)
    return np.asarray(select_neighbors(div, cand, k).neighbors)


def bench_shape(n: int, r: int, c: int, k: int, backend: str,
                seed: int = 0, verbose: bool = True) -> list:
    from repro.core import wire
    from repro.kernels import ops

    logp = _clustered_messengers(jax.random.key(seed), n, r, c)
    q_pool = min(n, max(2 * k, n // 2))
    div0 = ops.pairwise_kl(logp, backend=backend)
    nbrs0 = _graph_neighbors(div0, n, q_pool, k)
    fp32_bpm = r * c * 4

    rows = []
    for name in CODECS:
        codec = wire.as_codec(name)
        payload = codec.encode(logp, domain="log")
        dec = wire.decode(payload)
        bpm = wire.bytes_per_messenger(payload)
        kl = float(np.mean(np.diag(np.asarray(
            ops.pairwise_kl_pair(logp, dec, backend=backend)))))
        div1 = ops.pairwise_kl(dec, backend=backend)
        nbrs1 = _graph_neighbors(div1, n, q_pool, k)
        overlap = float(np.mean([
            len(set(nbrs0[i]) & set(nbrs1[i])) / k for i in range(n)]))
        row = {
            "codec": name, "n_clients": n, "ref_size": r, "n_classes": c,
            "bytes_per_messenger": bpm,
            "bytes_per_round_up": bpm * n,
            "ratio_vs_fp32": fp32_bpm / bpm,
            "roundtrip_kl": kl,
            "topk_overlap": overlap,
        }
        if name == "int8":
            # fused dequant->KL off the wire form vs decode-then-KL
            arrs = payload.arrays
            fused = ops.int8_pairwise_kl(arrs["q"], arrs["scale"],
                                         arrs["zp"], backend=backend)
            err = float(jnp.max(jnp.abs(fused - div1)))
            row["fused_kl_max_err"] = err
            row["fused_kl_s"] = _time(lambda: ops.int8_pairwise_kl(
                arrs["q"], arrs["scale"], arrs["zp"], backend=backend))
            row["decode_kl_s"] = _time(lambda: ops.pairwise_kl(
                wire.decode(payload), backend=backend))
        rows.append(row)
        if verbose:
            print(f"  R={r:4d} C={c:3d} {name:>8s}: "
                  f"{bpm:8.0f} B/msgr ({row['ratio_vs_fp32']:4.2f}x)  "
                  f"rt-KL {kl:.2e}  overlap {overlap:.3f}", flush=True)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=64, help="clients")
    ap.add_argument("--k", type=int, default=8, help="graph neighbors")
    ap.add_argument("--backend", choices=("pallas", "interpret", "jnp"),
                    default="jnp")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for the CI lane")
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args()

    shapes = SMOKE_SHAPES if args.smoke else SHAPES
    n = 16 if args.smoke else args.n
    print(f"== Messenger wire formats: bytes vs round-trip error vs graph "
          f"fidelity (N={n}, backend={args.backend}) ==", flush=True)
    rows = []
    for r, c in shapes:
        rows.extend(bench_shape(n, r, c, min(args.k, n - 1), args.backend))
        jax.clear_caches()

    int8_rows = [x for x in rows if x["codec"] == "int8"]
    acceptance = {
        "int8_ratio_vs_fp32_min": min(x["ratio_vs_fp32"]
                                      for x in int8_rows),
        "int8_topk_overlap_min": min(x["topk_overlap"] for x in int8_rows),
        "int8_ratio_ge_3p5": all(x["ratio_vs_fp32"] >= 3.5
                                 for x in int8_rows),
        "int8_overlap_ge_0p9": all(x["topk_overlap"] >= 0.9
                                   for x in int8_rows),
    }
    with open(args.out, "w") as f:
        json.dump({"rows": rows, "acceptance": acceptance}, f, indent=2)
    print(f"wire,{len(rows)},int8 {acceptance['int8_ratio_vs_fp32_min']:.2f}x"
          f" overlap>={acceptance['int8_topk_overlap_min']:.3f}"
          f" -> {args.out}")


if __name__ == "__main__":
    main()
