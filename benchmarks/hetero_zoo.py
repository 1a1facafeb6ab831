"""Heterogeneous model-zoo benchmark: per-architecture cohort costs in a
mixed federation.

CPU only: it starts one child process per device count, each with
forced host devices, and a TPU belongs to one process at a time.

Builds ONE mixed federation (4 families round-robined over the clients:
``mlp-s, resnet, transformer, ssm``) and measures, at N ∈ {64, 256}
clients × devices ∈ {1, 8}:

  * step      — one cohort training step per FAMILY, through the exact
                dispatch the runtime uses (each cohort's own (sub)mesh
                jit, its own per-family optimizer);
  * upload    — one messenger upload per family (the (n_f, R, C)
                soft-label batch the server actually receives);
  * final_acc — mean client accuracy after a short mixed training run
                (the end-to-end "heterogeneity costs nothing
                semantically" number next to the per-arch costs).

A device count is a process-level property (XLA fixes it at import), so
the parent spawns one child per ``--devices`` entry with
``XLA_FLAGS=--xla_force_host_platform_device_count=<d>`` and collects
JSON rows. Rows carry ``entry`` = family name (``mixed`` for the
train-run row) so ``benchmarks/trajectory.py`` folds them into per-arch
cells. Results land in ``BENCH_hetero.json``:

  PYTHONPATH=src python benchmarks/hetero_zoo.py           # d in 1,8
  PYTHONPATH=src python benchmarks/hetero_zoo.py --smoke   # CI

On the CPU container the fake host devices share the same cores — the
point is the parity story (every family runs the same sharded code path,
tiny buckets land on device subsets), not a speedup claim.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

OUT = "BENCH_hetero.json"
ZOO = "mlp-s,resnet,transformer,ssm"
DEFAULT_N = (64, 256)
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


def bench_child(sizes, n_dev: int, rounds: int, batch: int) -> list:
    """Runs inside a child process whose XLA_FLAGS pin the device count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import FederationConfig, FederationEngine, Protocol
    from repro.core.client import (cohort_messenger_upload, cohort_step,
                                   sharded_cohort_step,
                                   sharded_messenger_upload)
    from repro.data import make_splits
    from repro.data.pipeline import cohort_batch, cohort_batch_padded
    from repro.data.synthetic import _clustered_dataset
    from repro.models.zoo import build_zoo

    if jax.device_count() < n_dev:
        raise RuntimeError(f"need {n_dev} devices, have "
                           f"{jax.device_count()}")
    rows_out = []
    for n in sizes:
        ds = _clustered_dataset("hetero_bench", 0, n, 4, 4, 24, 30, 30,
                                skew=4.0)
        splits = make_splits(ds, seed=0)
        zoo = build_zoo(ZOO, ds.feature_len, ds.n_classes)
        config = FederationConfig(rounds=rounds, batch_size=batch,
                                  eval_every=max(1, rounds // 2),
                                  devices=n_dev if n_dev > 1 else None)
        engine = FederationEngine.build(ds, splits, zoo, None,
                                        Protocol("sqmd", rho=0.8, q=8, k=4),
                                        config=config, seed=1)
        fed = engine.fed
        n_all, r, c = fed.server.repo_logp.shape
        if fed.targets is None:
            fed.targets = jnp.full((n_all, r, c), 1.0 / c, jnp.float32)

        # --- per-family one-step / one-upload cost, through the exact
        # dispatch ClientRuntime uses (per-cohort (sub)mesh + optimizer) ---
        for coh in fed.cohorts:
            step = (cohort_step if coh.sharding is None
                    else sharded_cohort_step(coh.sharding.mesh))
            up = (cohort_messenger_upload if coh.sharding is None
                  else sharded_messenger_upload(coh.sharding.mesh))
            opt = coh.optimizer or fed.optimizer
            if coh.n_pad == 0:
                batch_d = cohort_batch(jax.random.key(5), coh.data, batch)
            else:
                batch_d = cohort_batch_padded(jax.random.key(5), coh.data,
                                              batch, coh.n_clients)
            ids = (coh.client_ids if coh.n_pad == 0 else coh.padded_ids)
            rows = jnp.asarray(ids)
            on = jnp.arange(coh.n_rows) < coh.n_clients
            tgt = fed.targets[rows]
            if (engine.mesh is not None and coh.sharding is not None
                    and coh.sharding.mesh.devices.size
                    < engine.mesh.devices.size):
                tgt = jax.device_put(tgt, coh.sharding)
            n_params = sum(int(np.prod(a.shape[1:]))
                           for a in jax.tree_util.tree_leaves(coh.params))
            t_step = _time(lambda: step(
                coh.apply_fn, opt, coh.params, coh.opt_state,
                batch_d["x"], batch_d["y"], fed.ref_x, tgt, on, 0.8,
                True)[2])
            t_up = _time(lambda: up(coh.apply_fn, coh.params, fed.ref_x))
            mesh_dev = (1 if coh.sharding is None
                        else coh.sharding.mesh.devices.size)
            row = {"entry": coh.family_name, "n_clients": n,
                   "devices": n_dev, "batch": batch,
                   "cohort_clients": coh.n_clients,
                   "cohort_devices": mesh_dev,
                   "params_per_client": n_params,
                   "step_s": t_step, "upload_s": t_up,
                   "steps_per_s": 1.0 / t_step}
            print(f"  N={n:4d} d={n_dev}  {coh.family_name:12s} "
                  f"({coh.n_clients:3d} clients, {n_params:6d} params): "
                  f"step {t_step*1e3:8.1f}ms  upload {t_up*1e3:7.1f}ms",
                  flush=True, file=sys.stderr)
            rows_out.append(row)

        # --- the end-to-end mixed run: accuracy is architecture-blind ---
        t0 = time.perf_counter()
        hist = engine.fit(splits)
        wall = time.perf_counter() - t0
        row = {"entry": "mixed", "n_clients": n, "devices": n_dev,
               "batch": batch, "rounds": rounds, "zoo": ZOO,
               "final_acc": float(hist.mean_acc[-1]),
               "train_s": wall,
               "rounds_per_s": rounds / wall}
        print(f"  N={n:4d} d={n_dev}  mixed fit: "
              f"acc={row['final_acc']:.4f} in {wall:.1f}s",
              flush=True, file=sys.stderr)
        rows_out.append(row)
        jax.clear_caches()
    return rows_out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, nargs="*",
                    help=f"client counts (default {DEFAULT_N})")
    ap.add_argument("--devices", type=int, nargs="*",
                    help=f"device counts (default {DEFAULT_DEVICES})")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (N=32, devices 1 and 2, "
                         "2 rounds)")
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--_child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.smoke:
        sizes = tuple(args.n) if args.n else (32,)
        devices = tuple(args.devices) if args.devices else (1, 2)
        rounds = 2
    else:
        sizes = tuple(args.n) if args.n else DEFAULT_N
        devices = tuple(args.devices) if args.devices else DEFAULT_DEVICES
        rounds = args.rounds

    if args._child:
        rows = bench_child(sizes, devices[0], rounds, args.batch)
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
               "--devices", str(d), "--rounds", str(rounds),
               "--batch", str(args.batch), "--n", *map(str, sizes)]
        out = subprocess.run(cmd, env=env, capture_output=True, text=True)
        if out.returncode != 0:
            raise RuntimeError(f"child (devices={d}) failed:\n{out.stderr}")
        sys.stderr.write(out.stderr)
        all_rows.extend(json.loads(out.stdout.strip().splitlines()[-1]))
    with open(args.out, "w") as f:
        json.dump(all_rows, f, indent=2)
    print(f"hetero_zoo,{len(all_rows)} rows,"
          f"devices={sorted({r['devices'] for r in all_rows})} "
          f"-> {args.out}")


if __name__ == "__main__":
    main()
