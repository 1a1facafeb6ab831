"""Chip smoke test: the SQMD federation's main path on one TPU chip.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # sharded federation vs single device

One process, no children. The phases, one printed line each:

  1. device      — a TPU is present and the kernels dispatch to Pallas;
  2. kernels     — the seven Pallas kernels, compiled, against their jnp
                   oracles at the Sleep-Cassette widths (N=32, R=240, C=3);
  3. sync        — 3 rounds of the sync engine on ``sc_like`` with the
                   mlp-s/resnet/transformer/ssm zoo and the SQMD policy;
  4. event       — the event clock on the same data with the delta graph,
                   IVF selection and the int8 uplink;
  5. serving     — mixed-client query batches from a snapshot of the
                   phase-4 engine, against ``evaluate``'s forward.

The last line is one JSON object naming the device. Any failed check
raises, so the process exits nonzero and prints no such line. It also
fails on a host without a TPU and under any ``REPRO_KERNEL_BACKEND``
other than ``pallas``.

``--chips 4`` runs only the phase-3 federation sharded over four chips
(``devices=4``) and the same seed on one device, and compares the final
accuracy, the per-client messengers and the top-K neighbour sets.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (AsyncFederationEngine, FederationConfig,  # noqa: E402
                        FederationEngine, Protocol, selection_matrix)
from repro.core.wire import Int8  # noqa: E402
from repro.data import make_splits, sc_like  # noqa: E402
from repro.kernels import dequant_kl, neighbor_mean, ops, pairwise_kl, ref  # noqa: E402
from repro.kernels import soft_ce  # noqa: E402
from repro.kernels.backend import ENV_VAR  # noqa: E402
from repro.models.zoo import build_zoo  # noqa: E402
from repro.serve import QueryEngine, SnapshotStore  # noqa: E402

ZOO = "mlp-s,resnet,transformer,ssm"
SEED = 0
# One bf16 pass rounds each fp32 matmul input to 8 significant bits, so a
# product is off by at most 2^-8 of its magnitude; an fp32 sum adds far
# less. A kernel's error is bounded by BF16_PASS times the sum of the
# magnitudes of the products that make each output element.
BF16_PASS = 2.0 ** -8
FP32_SLACK = 1e-5


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


# --------------------------------------------------------------------------
# phase 1: device
# --------------------------------------------------------------------------

def phase_device(chips: int, cache: str) -> dict:
    env = os.environ.get(ENV_VAR)
    check(env in (None, "", "pallas"),
          f"{ENV_VAR}={env!r}: the chip run takes the compiled kernels only")
    dev = jax.devices()
    check(dev[0].platform == "tpu",
          f"no TPU: jax.devices()[0].platform == {dev[0].platform!r}")
    check(len(dev) == chips, f"{len(dev)} devices visible, {chips} asked")
    backend = ops.default_backend()
    check(backend == "pallas", f"kernel backend resolved to {backend!r}")
    info = {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}
    print(f"device: kind={info['kind']} count={info['count']} "
          f"backend={backend} compile_cache={cache}",
          flush=True)
    return info


# --------------------------------------------------------------------------
# phase 2: kernels
# --------------------------------------------------------------------------

def _highest(fn, *args):
    """The jnp oracle at full fp32 matmul precision."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(fn)(*args))


def _cross_magnitude(lp_a, lp_b):
    """(U,M) sum over (r, c) of |p_a * logp_b| / R — what a bf16 pass of
    the KL cross term can be off by, before the BF16_PASS factor — plus
    the row-entropy term's own magnitude."""
    def mag(la, lb):
        u, r, c = la.shape
        pa = jnp.exp(la).reshape(u, r * c)
        cross = pa @ jnp.abs(lb).reshape(lb.shape[0], r * c).T
        row = jnp.sum(pa * jnp.abs(la.reshape(u, r * c)), axis=-1)
        return (cross + row[:, None]) / r
    return _highest(mag, lp_a, lp_b)


def _report(name: str, got, want, bound) -> str:
    err = np.abs(np.asarray(got) - want)
    check(np.isfinite(np.asarray(got)).all(), f"{name}: non-finite output")
    worst = float(np.max(err - bound))
    check(worst <= 0.0, f"{name}: max error {float(err.max()):.3e} exceeds "
                        f"its bound by {worst:.3e}")
    return f"{name}={float(err.max()):.3e}(tol {float(np.max(bound)):.2e})"


def phase_kernels(setup, u: int = 4) -> None:
    """Each kernel compiled (``interpret=False``) against its oracle, at
    the deployment's (N, R, C); strips take ``u`` upload rows.

    Bounds: the matmul kernels get BF16_PASS times the element's product
    magnitudes (see ``_cross_magnitude``); ``soft_ce`` has no matmul, so
    it gets fp32 rounding over its R-term sum."""
    ds = setup[0]
    n, r, c = ds.n_clients, len(ds.ref_y), ds.n_classes
    k1, k2, k3, k4 = jax.random.split(jax.random.key(SEED), 4)
    logp = jax.nn.log_softmax(jax.random.normal(k1, (n, r, c)) * 2.0, -1)
    labels = jax.random.randint(k2, (r,), 0, c)
    w = jax.random.uniform(k3, (n, n))
    w = w / w.sum(1, keepdims=True)
    nbrs = jax.random.randint(k4, (n, 8), 0, n)
    edges = w[:, :8] / w[:, :8].sum(1, keepdims=True)
    probs = jnp.exp(logp)
    wire = Int8().encode(logp).arrays
    q, s, z = wire["q"], wire["scale"], wire["zp"]
    deq = ref.int8_dequant_ref(q, s, z)
    lines = []

    got = pairwise_kl.pairwise_kl(logp, interpret=False)
    lines.append(_report("pairwise_kl", got,
                         _highest(ref.pairwise_kl_ref, logp),
                         BF16_PASS * _cross_magnitude(logp, logp)
                         + FP32_SLACK))
    got = pairwise_kl.pairwise_kl_pair(logp[:u], logp, interpret=False)
    lines.append(_report("pairwise_kl_pair", got,
                         _highest(ref.pairwise_kl_pair_ref, logp[:u], logp),
                         BF16_PASS * _cross_magnitude(logp[:u], logp)
                         + FP32_SLACK))
    got = soft_ce.soft_ce(logp, labels, interpret=False)
    want = _highest(ref.soft_ce_ref, logp, labels)
    lines.append(_report("soft_ce", got, want,
                         FP32_SLACK * np.maximum(np.abs(want), 1.0)))
    got = neighbor_mean.neighbor_mean(nbrs, edges, probs, interpret=False)
    want = _highest(ref.neighbor_mean_sparse_ref, nbrs, edges, probs)
    # an fp32 sum of K nonnegative terms: fp32 rounding of its magnitude
    lines.append(_report("neighbor_mean", got, want,
                         FP32_SLACK * np.maximum(want, 1.0)))
    got = neighbor_mean.neighbor_mean_dense(w, probs, interpret=False)
    want = _highest(ref.neighbor_mean_ref, w, probs)
    # w and probs are nonnegative, so the oracle is its own magnitude
    lines.append(_report("neighbor_mean_dense", got, want,
                         BF16_PASS * want + FP32_SLACK))
    got = dequant_kl.int8_pairwise_kl(q, s, z, interpret=False)
    lines.append(_report("int8_pairwise_kl", got,
                         _highest(ref.int8_pairwise_kl_ref, q, s, z),
                         BF16_PASS * _cross_magnitude(deq, deq)
                         + FP32_SLACK))
    got = dequant_kl.int8_pairwise_kl_pair(q[:u], s[:u], z[:u], q, s, z,
                                           interpret=False)
    lines.append(_report("int8_pairwise_kl_pair", got,
                         _highest(ref.int8_pairwise_kl_pair_ref,
                                  q[:u], s[:u], z[:u], q, s, z),
                         BF16_PASS * _cross_magnitude(deq[:u], deq)
                         + FP32_SLACK))
    print(f"kernels: N={n} R={r} C={c} max_abs_err " + " ".join(lines),
          flush=True)


# --------------------------------------------------------------------------
# phases 3-4: the federation, sync and event clocks
# --------------------------------------------------------------------------

def sc_setup(samples_per_client: int = 400):
    """The paper's Sleep-Cassette deployment at its own widths."""
    ds = sc_like(samples_per_client=samples_per_client)
    splits = make_splits(ds, seed=SEED, label_noise=0.3)
    zoo = build_zoo(ZOO, ds.feature_len, ds.n_classes)
    return ds, splits, zoo


def _protocol() -> Protocol:
    return Protocol("sqmd", rho=0.8, q=16, k=8)


def _summary(eng, wall: float) -> dict:
    h = eng.history
    acc = np.asarray(h.per_client_acc[-1])
    check(np.isfinite(h.mean_acc).all() and np.isfinite(acc).all(),
          "non-finite accuracy")
    check(eng.last_graph is not None, "the server never built a graph")
    edges = int(np.asarray(selection_matrix(eng.last_graph) > 0).sum())
    check(edges > 0, "the last graph has no edges")
    check(h.bytes_up[-1] > 0, "no messenger bytes went up")
    return {"acc": round(h.mean_acc[-1], 4), "edges": edges,
            "graph": h.graph_stats[-1], "server_rounds": h.server_rounds[-1],
            "bytes_up": h.bytes_up[-1], "wall_s": round(wall, 2)}


def run_sync(setup, devices=None, rounds: int = 3) -> FederationEngine:
    ds, splits, zoo = setup
    config = FederationConfig(rounds=rounds, batch_size=16, eval_every=1,
                              devices=devices)
    eng = FederationEngine.build(ds, splits, zoo, None, _protocol(),
                                 config=config, seed=SEED + 1)
    eng.fit(splits)
    return eng


def phase_sync(setup) -> None:
    t0 = time.perf_counter()
    eng = run_sync(setup)
    print("sync: " + json.dumps(_summary(eng, time.perf_counter() - t0)),
          flush=True)


def phase_event(setup, until: float = 3.0) -> AsyncFederationEngine:
    ds, splits, zoo = setup
    config = FederationConfig(rounds=int(until) + 1, batch_size=16,
                              eval_every=1, delta_graph=True,
                              selection="ivf", uplink="int8")
    t0 = time.perf_counter()
    eng = AsyncFederationEngine.build(ds, splits, zoo, None, _protocol(),
                                      config=config, seed=SEED + 1)
    eng.fit(splits, until=until)
    out = _summary(eng, time.perf_counter() - t0)
    check(eng.policy._ivf is not None, "the IVF index was never built")
    print("event: " + json.dumps(out), flush=True)
    return eng


# --------------------------------------------------------------------------
# phase 5: serving
# --------------------------------------------------------------------------

def phase_serving(eng, setup, batches: int = 4, per_batch: int = 24) -> None:
    """Served logits vs the evaluation forward (the vmapped apply over
    each cohort's stacked params). On the CPU the two agree bit for bit;
    on the chip they batch differently, and each matmul of the forward
    may round its inputs to bf16, so they agree to a few bf16 steps of
    the logits' magnitude."""
    _, splits, _ = setup
    store = eng.attach_snapshots(SnapshotStore())
    qe = QueryEngine(store)
    ref_logits = {}
    for coh in eng.fed.cohorts:
        xs = jnp.stack([jnp.asarray(splits[int(cid)].test_x)
                        for cid in coh.client_ids])
        out = np.asarray(jax.vmap(coh.apply_fn)(coh.real_params, xs))
        for row, cid in enumerate(coh.client_ids):
            ref_logits[int(cid)] = out[row]
    rng = np.random.default_rng(SEED)
    worst, scale, served, agree = 0.0, 1.0, 0, 0
    for b in range(batches):
        cids = rng.integers(0, eng.n_clients, per_batch)
        idx = rng.integers(0, len(splits[0].test_y), per_batch)
        xs = np.stack([np.asarray(splits[int(c)].test_x)[i]
                       for c, i in zip(cids, idx)])
        res = qe.serve(cids, xs, t=float(b))
        want = np.stack([ref_logits[int(c)][i] for c, i in zip(cids, idx)])
        check(np.isfinite(res.logits).all(), "non-finite served logits")
        worst = max(worst, float(np.abs(res.logits - want).max()))
        scale = max(scale, float(np.abs(want).max()))
        served += len(cids)
        agree += int((res.preds == np.argmax(want, -1)).sum())
    tol = 4 * BF16_PASS * scale
    check(worst <= tol, f"served logits off by {worst:.3e} > {tol:.3e}")
    print(f"serving: requests={served} batches={batches} "
          f"max_abs_err={worst:.3e} (tol {tol:.3e}) "
          f"argmax_agree={agree}/{served} version={store.version}",
          flush=True)


# --------------------------------------------------------------------------
# four chips: sharded federation vs the single-device reference
# --------------------------------------------------------------------------

def phase_sharded(setup, chips: int) -> None:
    t0 = time.perf_counter()
    sharded = run_sync(setup, devices=chips)
    t1 = time.perf_counter()
    single = run_sync(setup, devices=None)
    t2 = time.perf_counter()
    a, b = _summary(sharded, t1 - t0), _summary(single, t2 - t1)
    msg_err = float(np.abs(np.asarray(sharded.server.repo_logp)
                           - np.asarray(single.server.repo_logp)).max())
    na = np.asarray(sharded.last_graph.neighbors)
    nb = np.asarray(single.last_graph.neighbors)
    k = na.shape[1]
    overlap = float(np.mean([len(set(x) & set(y)) / k
                             for x, y in zip(na, nb)]))
    print(f"sharded: devices={chips} acc={a['acc']} vs devices=None "
          f"acc={b['acc']} messenger_max_abs_err={msg_err:.3e} "
          f"topk_overlap={overlap:.4f} wall_s={a['wall_s']}/{b['wall_s']}",
          flush=True)
    check(np.isfinite(msg_err), "non-finite messenger difference")
    # rounding moves a few neighbours at most; a wrong row placement
    # would leave about K/N of each set (0.25 here)
    check(overlap >= 0.75, f"top-K overlap {overlap:.3f} < 0.75")
    check(abs(a["acc"] - b["acc"]) <= 0.05,
          f"accuracy {a['acc']} vs {b['acc']} differs by more than 0.05")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded federation and its "
                         "single-device reference")
    args = ap.parse_args()
    info = phase_device(args.chips, enable_compile_cache())
    setup = sc_setup()
    if args.chips == 1:
        phase_kernels(setup)
        phase_sync(setup)
        phase_serving(phase_event(setup), setup)
    else:
        phase_sharded(setup, args.chips)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
