"""Client-axis sharding benchmark: cohort step + server graph build vs
device count.

CPU only: it starts one child process per device count, each with
forced host devices, and a TPU belongs to one process at a time.

Measures, at N ∈ {256, 1k, 4k} clients:

  * step    — one device-sharded ``cohort_step`` over a single stacked
              MLP cohort of N clients (the per-round client hot path);
  * graph   — one full Eq.2 divergence rebuild + SQMD pool selection
              (``build_graph``) with the divergence sharded row-wise
              over the same mesh.

A device count is a *process-level* property (XLA fixes it at import), so
the parent spawns one child per ``--devices`` entry with
``XLA_FLAGS=--xla_force_host_platform_device_count=<d>`` and collects one
JSON row per (N, d). Results land in ``BENCH_shard.json`` (repo root by
default):

  PYTHONPATH=src python benchmarks/shard_scale.py                # d in 1,8
  PYTHONPATH=src python benchmarks/shard_scale.py --devices 1 2 4 8
  PYTHONPATH=src python benchmarks/shard_scale.py --smoke        # CI

On the 2-core CPU container the fake host devices share the same cores —
the point of the CPU numbers is the overhead/parity story (sharded code
path, real timings), not a speedup claim; on a real multi-chip platform
the same flag-free code scales the client axis.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

OUT = "BENCH_shard.json"
DEFAULT_N = (256, 1024, 4096)
DEFAULT_DEVICES = (1, 8)


def _time(fn, reps=3):
    """Min-of-reps wall time (min is the least noisy estimator on a
    shared box — noise only ever adds time)."""
    import jax
    jax.block_until_ready(fn())          # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def bench_child(sizes, n_dev: int, ref_size: int, classes: int,
                batch: int) -> list:
    """Runs inside a child process whose XLA_FLAGS pin the device count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.client import (cohort_step, sharded_cohort_step,
                                   sharded_messenger_upload,
                                   cohort_messenger_upload)
    from repro.core.similarity import divergence_matrix
    from repro.data.pipeline import cohort_batch
    from repro.models.mlp import MLPConfig, mlp_family
    from repro.optim import sgd
    from repro.sharding import (client_sharding, ghost_pad_stack,
                                ghost_rows, make_client_mesh)

    assert jax.device_count() >= n_dev, (jax.device_count(), n_dev)
    mesh = make_client_mesh(n_dev) if n_dev > 1 else None
    feat, m_samples = 24, 32
    init_fn, apply_fn = mlp_family(MLPConfig("bench", feat, (64,), classes))
    opt = sgd(0.05, momentum=0.9)
    rows = []
    for n in sizes:
        key = jax.random.key(0)
        keys = jax.random.split(key, n)
        params = jax.vmap(init_fn)(keys)
        opt_state = jax.vmap(opt.init)(params)
        data = {"x": jax.random.normal(jax.random.key(1),
                                       (n, m_samples, feat)),
                "y": jax.random.randint(jax.random.key(2),
                                        (n, m_samples), 0, classes)}
        ref_x = jax.random.normal(jax.random.key(3), (ref_size, feat))
        targets = jnp.full((n, ref_size, classes), 1.0 / classes)
        trainable = jnp.ones((n,), bool)
        logp = jax.nn.log_softmax(
            jax.random.normal(jax.random.key(4), (n, ref_size, classes))
            * 2.0, -1)

        if mesh is None:
            step, upload = cohort_step, cohort_messenger_upload
        else:
            step = sharded_cohort_step(mesh)
            upload = sharded_messenger_upload(mesh)
            pad = ghost_rows(n, n_dev)
            sh = client_sharding(mesh)
            put = lambda t: jax.device_put(  # noqa: E731
                ghost_pad_stack(t, pad), sh)
            params, opt_state, data = put(params), put(opt_state), put(data)
            targets = put(targets)
            # already padded by hand (ghosts must be False, not a replica
            # of the last row) — plain device_put, no ghost_pad_stack
            trainable = jax.device_put(
                jnp.concatenate([trainable, jnp.zeros((pad,), bool)]), sh)
        batch_d = cohort_batch(jax.random.key(5), data, batch)

        t_step = _time(lambda: step(
            apply_fn, opt, params, opt_state, batch_d["x"], batch_d["y"],
            ref_x, targets, trainable, 0.8, True)[2])
        t_up = _time(lambda: upload(apply_fn, params, ref_x))
        t_graph = _time(lambda: divergence_matrix(logp, mesh=mesh))
        row = {"n_clients": n, "devices": n_dev,
               "ref_size": ref_size, "n_classes": classes, "batch": batch,
               "step_s": t_step, "upload_s": t_up, "graph_build_s": t_graph,
               "steps_per_s": 1.0 / t_step}
        print(f"  N={n:6d} d={n_dev}: step {t_step*1e3:8.1f}ms  "
              f"upload {t_up*1e3:7.1f}ms  graph {t_graph*1e3:8.1f}ms",
              flush=True, file=sys.stderr)
        rows.append(row)
        jax.clear_caches()
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, nargs="*",
                    help=f"client counts (default {DEFAULT_N})")
    ap.add_argument("--devices", type=int, nargs="*",
                    help=f"device counts (default {DEFAULT_DEVICES})")
    ap.add_argument("--ref-size", type=int, default=64)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (N=256, devices 1 and 2)")
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--_child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.smoke:
        sizes = tuple(args.n) if args.n else (256,)
        devices = tuple(args.devices) if args.devices else (1, 2)
    else:
        sizes = tuple(args.n) if args.n else DEFAULT_N
        devices = tuple(args.devices) if args.devices else DEFAULT_DEVICES

    if args._child:
        rows = bench_child(sizes, devices[0], args.ref_size, args.classes,
                           args.batch)
        print(json.dumps(rows))
        return

    all_rows = []
    for d in devices:
        env = dict(os.environ)
        # replace (not append) any inherited device-count flag — a
        # duplicate flag would make the child's XLA init ambiguous
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith(
                     "--xla_force_host_platform_device_count")]
        flags.append(f"--xla_force_host_platform_device_count={d}")
        env["XLA_FLAGS"] = " ".join(flags)
        print(f"== devices={d} (child process) ==", flush=True)
        cmd = [sys.executable, os.path.abspath(__file__), "--_child",
               "--devices", str(d), "--ref-size", str(args.ref_size),
               "--classes", str(args.classes), "--batch", str(args.batch),
               "--n", *map(str, sizes)]
        out = subprocess.run(cmd, env=env, capture_output=True, text=True)
        if out.returncode != 0:
            raise RuntimeError(f"child (devices={d}) failed:\n{out.stderr}")
        sys.stderr.write(out.stderr)
        all_rows.extend(json.loads(out.stdout.strip().splitlines()[-1]))
    with open(args.out, "w") as f:
        json.dump(all_rows, f, indent=2)
    print(f"shard_scale,{len(all_rows)} rows,"
          f"devices={sorted({r['devices'] for r in all_rows})} "
          f"-> {args.out}")


if __name__ == "__main__":
    main()
