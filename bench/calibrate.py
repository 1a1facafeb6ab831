"""Readings that the output check's limits are set from. Not part of any
benchmark run.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds 2]
                              [--control 4,5,6] [--fault 7,8,9]
                              [--out readings.jsonl]

Each ``--seeds`` seed is one run of the cell through ``harness.run``, the
comparison ``correct`` uses, with a window of ``--seconds``; it prints the
numbers compared. ``--control`` seeds run the control in the program's
place: for a training cell the reference computed in bfloat16, for the
server cell the program with its dense16 wire (the configuration's
float32 one step down). ``--fault`` seeds plant a fault: for a training
cell the reference trained on half of each local batch, for the server
cell a delta update that leaves the divergence cache unchanged. One JSON
line per reading on standard output, and appended to ``--out`` where
given.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, ref  # noqa: E402
from bench.drivers import server_delta, sync_rounds  # noqa: E402


def _emit(rec: dict, out) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def program(cell, seed: int, seconds: float, driver=None) -> dict:
    """The numbers one benchmark run of ``cell`` compares, with
    ``driver`` (an instance) in the place of the cell's own."""
    args = harness.parse(["--workload", cell.name, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", "0"])
    res = harness.run(args, time.perf_counter(), root=_ROOT, driver=driver)
    return {k: c["value"] for k, c in res["checks"].items()}


def training_reading(cell, seed: int, dtype, fraction: float) -> dict:
    """A training cell's numbers for the reference run in ``dtype`` on
    ``fraction`` of each batch, against the float32 reference."""
    drv = sync_rounds.Driver(cell.config, cell.traffic, seed)
    from bench import data
    fed = data.make_federation(cell.config, seed)
    rounds = drv.check_rounds
    base = ref.federation_rounds(cell.config, fed, drv._weights(),
                                 drv._rng(), rounds)
    alt = ref.federation_rounds(cell.config, fed, drv._weights(), drv._rng(),
                                rounds, dtype=dtype, batch_fraction=fraction)
    names = list(cell.config["families"])
    delta = {f: [b - a for a, b in zip(jax.tree.leaves(alt["weights0"][f]),
                                       jax.tree.leaves(
                                           alt["weights"][-1][f]))]
             for f in names}
    return sync_rounds.reference_gaps(
        cell.config, base, alt["loss"],
        {f: jax.tree.leaves(alt["grads"][f]) for f in names}, delta)


class StaleCache(server_delta.Driver):
    """The server cell with a fault planted after set-up: the delta update
    returns the divergence cache unchanged."""

    def setup(self):
        from repro.core import similarity
        super().setup()
        self._update = similarity.update_divergence_cache
        similarity.update_divergence_cache = lambda cache, *a, **k: cache

    def release(self):
        from repro.core import similarity
        similarity.update_divergence_cache = self._update
        super().release()


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    cell = harness.load_cell(args.workload)
    training = cell.traffic["driver"] == "sync_rounds"

    def emit(kind, seed, t, got):
        _emit({"cell": cell.name, "kind": kind, "seed": seed,
               "readings": got, "s": time.perf_counter() - t}, args.out)

    for seed in seeds(args.seeds):
        t = time.perf_counter()
        emit("program", seed, t, program(cell, seed, args.seconds))
    for seed in seeds(args.control):
        t = time.perf_counter()
        if training:
            got = training_reading(cell, seed, jnp.bfloat16, 1.0)
        else:
            cfg = dict(cell.config, uplink="dense16", downlink="dense16")
            got = program(cell, seed, args.seconds,
                          server_delta.Driver(cfg, cell.traffic, seed))
        emit("control", seed, t, got)
    for seed in seeds(args.fault):
        t = time.perf_counter()
        if training:
            kind = "fault:half_batch"
            got = training_reading(cell, seed, jnp.float32, 0.5)
        else:
            kind = "fault:stale_cache"
            got = program(cell, seed, args.seconds,
                          StaleCache(cell.config, cell.traffic, seed))
        emit(kind, seed, t, got)
    print(f"calibrate: {time.perf_counter() - T_START:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
