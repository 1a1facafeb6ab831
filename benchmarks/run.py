"""Benchmark runner — one function per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` CSV lines (one per bench).

Each bench runs in its OWN subprocess: a long federation sweep accumulates
jit executables faster than the host's RAM likes. CPU only: a TPU
belongs to one process at a time.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only table3
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

BENCHES = {
    "table3": "benchmarks.table3_accuracy",   # Table III  (RQ1)
    "fig2": "benchmarks.fig2_sparsity",       # Fig. 2     (RQ2)
    "fig3": "benchmarks.fig3_hyperparams",    # Fig. 3     (RQ3)
    "fig4": "benchmarks.fig4_async",          # Fig. 4     (RQ4)
    "server_kernels": "benchmarks.server_kernels",
    "roofline": "benchmarks.roofline",
    "wire": "benchmarks.wire",                # messenger codec bytes/fidelity
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=tuple(BENCHES), nargs="*")
    args = ap.parse_args()
    names = args.only or list(BENCHES)
    print("name,us_per_call,derived")
    failed = []
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    for name in names:
        t0 = time.time()
        r = subprocess.run([sys.executable, "-m", BENCHES[name]], env=env)
        if r.returncode != 0:
            failed.append(name)
            print(f"{name},0,FAILED:exit={r.returncode}", flush=True)
        print(f"# {name} wall: {time.time()-t0:.1f}s", flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
