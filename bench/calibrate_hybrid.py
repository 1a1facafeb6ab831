"""Readings that ``sc-long``'s output-check limits are set from, as
``bench/calibrate.py`` takes them for the other cells. Not part of any
benchmark run.

    python bench/calibrate_hybrid.py --seeds 1,2,3 [--seconds 10]
                                     [--traced 1] [--more 4,5]
                                     [--control 1,2] [--fault 1,2]
                                     [--flips 1] [--out readings.jsonl]

Each ``--seeds`` seed is one run of the cell through ``harness.run`` (the
comparison ``correct`` uses; ``--traced`` ones with ``--trace 1``), all
in this process with one zoo, so the steps compile once; it prints the
numbers compared, the messenger's gap ``msg`` (which decides nothing) and
the run's metrics. ``--more`` seeds are such runs with 2 s windows.

``--control`` seeds compare the reference computed in bfloat16 with the
float32 one; ``--fault`` seeds read the reference trained on half of each
local batch (the program leaving its state unchanged reads 1 for
``grad`` and ``change`` by construction; ``bench/tests`` plants it). A
seed that also ran the program reuses that run's float32 reference.
``--flips`` seeds that ran the program count, in each expert layer, the
token choices of the messenger's forward (the 240 reference series) that
differ between the program's routing and the reference's: at the
starting weights and at the weights after the check rounds, each side
routing its own layer inputs. One JSON line per reading on standard
output, and appended to ``--out`` where given.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import data, harness, ref  # noqa: E402
from bench import ref_nemotron_h as href  # noqa: E402
from bench.calibrate import _emit  # noqa: E402
from bench.drivers import sync_rounds_hybrid  # noqa: E402

CELL = "sc-long"
SEQS = 40            # series a block of the routing comparison


def program(cell, seed: int, seconds: float, trace: int = 0):
    """One run of the cell; returns (readings, driver)."""
    args = harness.parse(["--workload", cell.name, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
    drv = sync_rounds_hybrid.Driver(cell.config, cell.traffic, seed)
    res = harness.run(args, time.perf_counter(), root=_ROOT, driver=drv)
    got = {k: c["value"] for k, c in res["checks"].items()}
    got.update({k: m["value"] for k, m in res["metrics"].items()})
    got.update(msg=drv.msg_gap, correct=res["correct"],
               check_s=res["check_s"], device=res["device"],
               breakdown=res.get("breakdown"))
    return got, drv


def _one_zoo():
    """Every run of this process builds its zoo once: the same family
    functions, so the jitted steps compile once."""
    import functools
    from repro.models import zoo
    zoo.build_zoo = functools.lru_cache(maxsize=None)(zoo.build_zoo)


class _KeepBase:
    """Keeps the last float32, full-batch reference the driver's check
    computed, so the control and fault readings of that seed reuse it."""

    def __init__(self):
        self.last = None
        self._rounds = href.federation_rounds
        href.federation_rounds = self

    def __call__(self, *a, **k):
        out = self._rounds(*a, **k)
        if k.get("dtype", jnp.float32) == jnp.float32 and \
                k.get("batch_fraction", 1.0) == 1.0:
            self.last = out
        return out


def reference_readings(cell, seed: int, alts, base=None) -> list:
    """The numbers for the reference run in each ``(dtype, fraction of
    each batch)`` of ``alts``, against one float32 reference (``base``,
    else computed here)."""
    drv = sync_rounds_hybrid.Driver(cell.config, cell.traffic, seed)
    fed = data.make_federation(cell.config, seed)
    rounds = drv.check_rounds
    if base is None:
        base = href.federation_rounds(cell.config, fed, drv._weights(),
                                      drv._rng(), rounds)
    names = list(cell.config["families"])
    out = []
    for dtype, fraction in alts:
        alt = href.federation_rounds(cell.config, fed, drv._weights(),
                                     drv._rng(), rounds, dtype=dtype,
                                     batch_fraction=fraction)
        delta = {f: [b - a for a, b in zip(
            jax.tree.leaves(alt["weights0"][f]),
            jax.tree.leaves(alt["weights"][f]))] for f in names}
        out.append(sync_rounds_hybrid.reference_gaps(
            cell.config, base, alt["loss"],
            {f: jax.tree.leaves(alt["grads"][f]) for f in names}, delta,
            alt["msg"]))
    return out


# --------------------------------------------------------------------------
# routing: the program's token choices against the reference's
# --------------------------------------------------------------------------

def _program_ids(cfg: dict):
    """fn(p, x) -> each expert layer's top-k ids (n_moe, T, k), by the
    program's own layers at its matmul precision."""
    from repro.models import ffn, transformer, zoo
    from repro.models.common import rmsnorm
    mcfg = zoo.NEMOTRON_H
    seq = -(-cfg["series_length"] // cfg["patch"])

    @jax.jit
    def ids(p, x):
        h = zoo._to_tokens(x, cfg["patch"], seq) @ p["embed_w"] \
            + p["embed_b"]
        pos = jnp.arange(seq, dtype=jnp.int32)
        out = []
        for i, kind in enumerate(mcfg.layer_pattern):
            lp = jax.tree.map(lambda a: a[0], p["stack"]["groups"][f"pos{i}"])
            if kind == "moe":
                hn = rmsnorm(lp["norm1"], h, mcfg.norm_eps)
                out.append(ffn.sigmoid_route(
                    lp["mixer"], mcfg, hn.reshape(-1, hn.shape[-1]))[0])
            h = transformer.apply_layer(lp, mcfg, kind, h, pos)[0]
        return jnp.stack(out)
    return ids


def _reference_ids(cfg: dict):
    """fn(p, x) -> the same ids by ``bench/ref_nemotron_h.py``'s layers, at
    HIGHEST precision in float32."""
    seq, patch = -(-cfg["series_length"] // cfg["patch"]), cfg["patch"]
    eps = cfg["layer_norm_epsilon"]

    @jax.jit
    def ids(p, x):
        xp = jnp.pad(x, ((0, 0), (0, seq * patch - x.shape[1])))
        h = ref._ein("bsp,pd->bsd", xp.reshape(x.shape[0], seq, patch),
                     p["embed_w"]) + p["embed_b"]
        out = []
        for i, kind in enumerate(href._pattern(cfg)):
            lp = jax.tree.map(lambda a: a[0], p["stack"]["groups"][f"pos{i}"])
            hn = href._rms(lp["norm1"]["scale"], h, eps)
            if kind == "E":
                scores = jax.nn.sigmoid(ref._mm(
                    hn.reshape(-1, hn.shape[-1]), lp["mixer"]["router"]))
                out.append(jax.lax.top_k(scores + lp["mixer"]["router_bias"],
                                         cfg["num_experts_per_tok"])[1])
            h = h + href._OPS[kind](cfg, lp["mixer"], hn)
        return jnp.stack(out)

    def highest(p, x):
        with jax.default_matmul_precision("highest"):
            return ids(p, x)
    return highest


def _differ(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Per layer, the choices of ``a`` (n, T, k) that ``b`` lacks."""
    hit = (a[..., :, None] == b[..., None, :]).any(-1)
    return (~hit).sum(axis=(1, 2))


def choice_flips(cfg: dict, weights: dict, ref_x) -> dict:
    """Token choices in which the program's routing and the reference's
    differ, per expert layer, for each named pair of hybrid weights
    ``(program's, reference's)`` (stacked over the hybrid clients; the
    first is read) on the reference series."""
    prog, want = _program_ids(cfg), _reference_ids(cfg)
    out = {}
    for name, (wp, wr) in weights.items():
        wp, wr = (jax.device_put(jax.tree.map(lambda a: np.asarray(
            a[0], np.float32), w)) for w in (wp, wr))
        n = 0
        for s in range(0, len(ref_x), SEQS):
            x = jnp.asarray(ref_x[s:s + SEQS], jnp.float32)
            a = np.asarray(prog(wp, x))
            n = n + _differ(a, np.asarray(want(wr, x)))
        out[name] = n.tolist()
    out["choices_per_layer"] = int(len(ref_x) * -(-cfg["series_length"]
                                   // cfg["patch"])
                                   * cfg["num_experts_per_tok"])
    return out


def flips_of_run(cell, drv, base) -> dict:
    """``choice_flips`` at the starting weights and after the check
    rounds, for the first hybrid client of a finished run."""
    f = href.FAMILY
    w0 = drv._weights()[f]
    treedef = jax.tree.structure(w0)
    w_end = jax.tree.unflatten(treedef, [
        np.asarray(a) + d for a, d in zip(jax.tree.leaves(w0),
                                          drv.prog_delta[f])])
    return choice_flips(cell.config,
                        {"start": (w0, w0),
                         "after_check": (w_end, base["weights"][f])},
                        drv.data.ref_x)


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", default="")
    ap.add_argument("--more", default="",
                    help="sound seeds run after --seeds, with 2 s windows")
    ap.add_argument("--traced", default="",
                    help="seeds of --seeds to run with --trace 1")
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--flips", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    cell = harness.load_cell(CELL)
    _one_zoo()
    keep = _KeepBase()

    def emit(kind, seed, t, got):
        _emit({"cell": CELL, "kind": kind, "seed": seed, "readings": got,
               "s": time.perf_counter() - t}, args.out)

    control, fault, flips = (seeds(args.control), seeds(args.fault),
                             seeds(args.flips))
    traced = seeds(args.traced)

    def references(seed, base):
        kinds = ([("control", jnp.bfloat16, 1.0)] if seed in control
                 else []) + ([("fault:half_batch", jnp.float32, 0.5)]
                             if seed in fault else [])
        if not kinds:
            return
        t = time.perf_counter()
        got = reference_readings(cell, seed, [k[1:] for k in kinds], base)
        for (kind, _, _), g in zip(kinds, got):
            emit(kind, seed, t, g)

    ran = []
    runs = [(s, args.seconds) for s in seeds(args.seeds)] + \
        [(s, 2.0) for s in seeds(args.more)]
    for seed, seconds in runs:
        t = time.perf_counter()
        got, drv = program(cell, seed, seconds, trace=int(seed in traced))
        emit("program", seed, t, got)
        ran.append(seed)
        if seed in flips:
            t = time.perf_counter()
            emit("flips", seed, t, flips_of_run(cell, drv, keep.last))
        del drv
        references(seed, keep.last)
        keep.last = None
    for seed in sorted(set(control + fault) - set(ran)):
        references(seed, None)
    print(f"calibrate: {time.perf_counter() - T_START:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
