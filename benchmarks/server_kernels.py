"""Framework benchmark: server-phase kernel scaling (N, R, C sweeps).

Times the jnp runtime path on CPU and reports the analytic TPU roofline of
the Pallas path (the kernels are MXU matmuls; see DESIGN.md §4):
  pairwise_kl: 2·N²·R·C flops; neighbor_mean: 2·N·K·R·C; soft_ce: ~5·N·R·C.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import ensure_out
from repro.kernels import ops

PEAK = 197e12

GRID = [
    # (N, R, C)
    (32, 240, 3),          # the paper's SC scale
    (128, 512, 10),
    (512, 1024, 10),
    (1024, 1024, 100),     # production fleet scale
]


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps


def run(verbose=True):
    rows = []
    for n, r, c in GRID:
        key = jax.random.key(0)
        logits = jax.random.normal(key, (n, r, c)) * 2
        logp = jax.nn.log_softmax(logits, -1)
        labels = jax.random.randint(jax.random.key(1), (r,), 0, c)
        k = min(8, n - 1)
        nbrs = jax.random.randint(jax.random.key(2), (n, k), 0, n)
        w = jnp.full((n, k), 1.0 / k)
        probs = jnp.exp(logp)

        t_kl = _time(lambda a: ops.pairwise_kl(a, backend="jnp"), logp)
        t_ce = _time(lambda a: ops.soft_ce(a, labels, backend="jnp"), logp)
        t_nm = _time(lambda a: ops.neighbor_mean(nbrs, w, a, backend="jnp"),
                     probs)
        kl_flops = 2.0 * n * n * r * c
        tpu_us = kl_flops / PEAK * 1e6
        rows.append({
            "N": n, "R": r, "C": c,
            "pairwise_kl_cpu_us": t_kl * 1e6,
            "soft_ce_cpu_us": t_ce * 1e6,
            "neighbor_mean_cpu_us": t_nm * 1e6,
            "pairwise_kl_flops": kl_flops,
            "pairwise_kl_tpu_roofline_us": tpu_us,
        })
        if verbose:
            print(f"  N={n:5d} R={r:5d} C={c:4d}: kl={t_kl*1e6:9.0f}us "
                  f"ce={t_ce*1e6:8.0f}us nm={t_nm*1e6:8.0f}us "
                  f"(TPU roofline {tpu_us:7.2f}us)", flush=True)
    return rows


def main():
    t0 = time.time()
    print("== Server kernel scaling ==", flush=True)
    rows = run()
    d = ensure_out()
    with open(f"{d}/server_kernels.json", "w") as f:
        json.dump(rows, f, indent=2)
    big = rows[-1]
    print(f"server_kernels,{big['pairwise_kl_cpu_us']:.0f},"
          f"N={big['N']}_tpu_roofline_us={big['pairwise_kl_tpu_roofline_us']:.1f}")
    return rows


if __name__ == "__main__":
    main()
