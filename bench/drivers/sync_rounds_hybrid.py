"""Driver: rounds of the program's sync engine on the
``sc-nemotron3-nano`` federation, whose client 0 runs the ``nemotron-h``
family and clients 1-31 the other four families round-robin.

As ``sync_rounds``: set-up builds one ``FederationEngine`` from the
configuration's data and families, gives it weights and a batch-sampling
key made from the seed, and drives it through ``check_rounds`` rounds
with ``run_round`` (the first without distillation), which the output
check compares with ``bench/ref_nemotron_h.py``; the window then goes on
with the same engine. Set-up resolves the families first, so a program
without ``nemotron-h`` fails before any data or weights are made.

The hybrid's cohort step donates its params and optimizer state, so what
the check reads of them (the starting weights, the first gradient, the
weights after the check rounds) is copied to the host as it goes by. The
check compares ``loss``, ``grad`` and ``change`` over every family, the
hybrid's leaves included. The hybrid client's messenger after every
check round is compared element by element too (``msg_gap``), and kept
as ``self.msg_gap`` for ``bench/calibrate_hybrid.py``; it decides
nothing, since no planted fault reads far enough above the sound runs'
gaps for a limit to lie between them (``PERF.md`` §2).

The window resets the program's counters and reads them once after it:
the held experts' token choices give ``moe_load_max.nemotron-h`` and the
routed part of the round's FLOPs (``bench/flops_hybrid.py``).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import compare, data, flops_hybrid
from bench import ref_nemotron_h as href
from bench.drivers import sync_rounds

FAMILY = href.FAMILY


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


class Driver(sync_rounds.Driver):

    def _weights(self):
        return href.init_weights(self.cfg,
                                 jax.random.key(data.sub_seed(self.seed, 5)))

    def setup(self) -> None:
        from repro.core import FederationConfig, FederationEngine, Protocol
        from repro.core import runtime
        from repro.data.partition import ClientSplit
        from repro.data.synthetic import FederatedDataset
        from repro.models.zoo import build_zoo

        cfg = self.cfg
        names = list(cfg["families"])
        zoo = build_zoo(",".join(names), cfg["series_length"],
                        cfg["n_classes"])
        self.data = fed = data.make_federation(cfg, self.seed)
        ds = FederatedDataset(
            "bench", cfg["n_classes"], cfg["series_length"],
            [c.train_x for c in fed.clients], [c.train_y for c in fed.clients],
            fed.ref_x, fed.ref_y, fed.cluster)
        splits = [ClientSplit(c.train_x, c.train_y, c.val_x, c.val_y,
                              c.test_x, c.test_y) for c in fed.clients]
        proto = cfg["protocol"]
        eng = FederationEngine.build(
            ds, splits, zoo, href.who(cfg),
            Protocol("sqmd", rho=proto["rho"], q=proto["q"], k=proto["k"]),
            config=FederationConfig(
                rounds=1, batch_size=cfg["batch_size"],
                local_steps=cfg["local_steps"], uplink=cfg["uplink"],
                downlink=cfg["downlink"]),
            seed=0)
        self.w0 = _host(self._weights())
        for coh in eng.fed.cohorts:
            want = jax.tree.map(lambda a: (a.shape, a.dtype),
                                self.w0[coh.family_name])
            have = jax.tree.map(lambda a: (a.shape, np.dtype(a.dtype)),
                                coh.params)
            if want != have:
                raise ValueError(f"family {coh.family_name}: the program's "
                                 f"weights are not the configuration's "
                                 f"shapes: {have} vs {want}")
            coh.params = None
            coh.params = jax.device_put(self.w0[coh.family_name])
        eng.fed.rng = self._rng()
        self.eng = eng
        self.hybrid = np.flatnonzero(np.array(href.who(cfg)) == FAMILY)
        # the check rounds go through run_round; the cohort steps'
        # per-client losses, which the engine drops, are kept on the way
        losses, self.msg = [], []
        steps = {k: getattr(runtime, k)
                 for k in ("cohort_step", "expert_cohort_step")}

        def keeping(step):
            def keep_loss(*a, **k):
                out = step(*a, **k)
                losses.append(out[2])
                return out
            return keep_loss

        for k, step in steps.items():
            setattr(runtime, k, keeping(step))
        try:
            for _ in range(self.check_rounds):
                self._round()
                self.msg.append(np.asarray(
                    eng.fed.server.repo_logp[self.hybrid], np.float32))
                if self.round == 1:
                    self.grad1 = {c.family_name: sync_rounds.first_gradient(
                        cfg["families"][c.family_name]["optimizer"],
                        c.opt_state) for c in eng.fed.cohorts}
        finally:
            for k, step in steps.items():
                setattr(runtime, k, step)
        self.loss = losses
        self.order = [c.family_name for c in eng.fed.cohorts]
        self.w_end = {c.family_name: _host(c.params)
                      for c in eng.fed.cohorts}
        self._block()

    # -- the window --------------------------------------------------------
    def window(self, seconds: float) -> dict:
        counters = self.eng.clients.counters
        counters.reset()
        start = self.round
        t0 = time.perf_counter()
        while True:
            self._round()
            if time.perf_counter() - t0 >= seconds:
                break
        self._block()
        wall = time.perf_counter() - t0
        n = self.round - start
        load = counters.read().get(f"expert_load.{FAMILY}")
        per_round = None if load is None else load / n
        out = {"e2e": {"round_ms": 1e3 * wall / n}, "attempted": n,
               "failed": 0,
               "counters": {"rounds": n, "wall_s": wall,
                            "round_flops": flops_hybrid.sync_round(
                                self.cfg, None if load is None
                                else [per_round.sum()])}}
        if per_round is not None:
            out["counters"]["expert_load"] = per_round.tolist()
        return out

    # -- the output check --------------------------------------------------
    def release(self) -> None:
        names, n_fam = self.order, len(self.order)
        self.prog_loss = [
            {f: np.asarray(self.loss[r * n_fam + i], np.float32)
             for i, f in enumerate(names)}
            for r in range(self.check_rounds)]
        self.prog_grad = self.grad1
        self.prog_delta = {
            f: [b - a for a, b in zip(jax.tree.leaves(self.w0[f]),
                                      jax.tree.leaves(self.w_end[f]))]
            for f in names}
        del self.eng, self.loss, self.grad1, self.w_end, self.w0

    def check(self):
        out = href.federation_rounds(self.cfg, self.data, self._weights(),
                                     self._rng(), self.check_rounds)
        gaps = reference_gaps(self.cfg, out, self.prog_loss, self.prog_grad,
                              self.prog_delta, self.msg)
        self.msg_gap = gaps.pop("msg")
        return sorted(gaps.items())


def reference_gaps(cfg: dict, out: dict, prog_loss, prog_grad, prog_delta,
                   prog_msg) -> dict:
    """``sync_rounds``' training gaps over every family, and ``msg``: the
    largest ``msg_gap`` between the hybrid clients' messengers and the
    reference's over the check rounds."""
    names = list(cfg["families"])
    ref_grad = {f: jax.tree.leaves(out["grads"][f]) for f in names}
    ref_delta = {f: [b - a for a, b in zip(jax.tree.leaves(
        out["weights0"][f]), jax.tree.leaves(out["weights"][f]))]
        for f in names}
    gaps = compare.training(prog_loss, out["loss"], prog_grad, ref_grad,
                            prog_delta, ref_delta)
    gaps["msg"] = max(msg_gap(p, r) for p, r in zip(prog_msg, out["msg"]))
    return gaps


def msg_gap(prog, ref) -> float:
    """Largest element-wise gap between two messengers' log-probabilities,
    each against the reference's magnitude or 1 nat, whichever is larger:
    an absolute gap for the likely classes, a relative one for the
    unlikely, whose log-probabilities a saturated messenger (a first Adam
    step at lr 3e-3 sends them to -50 and below) carries far out."""
    p = np.asarray(prog, np.float64)
    r = np.asarray(ref, np.float64)
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1.0)))
