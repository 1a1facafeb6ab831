"""Driver: rounds of the program's sync engine, every client every round.

Set-up builds one ``FederationEngine`` from the configuration's data and
families, gives it weights and a batch-sampling key made from the seed,
and drives it through ``check_rounds`` rounds with ``run_round``: the
first without distillation and the rest with it, so every program the
window runs is compiled there. Those rounds are the ones the output
check compares with the reference; the window then goes on with the
same engine. Each round uploads every client's messenger and fires a
full rebuild of the collaboration graph.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import compare, data, flops, ref


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg = config
        self.seed = seed
        self.check_rounds = int(traffic["check_rounds"])
        self.round = 0

    # -- set-up ------------------------------------------------------------
    def _weights(self):
        cfg = self.cfg
        names = list(cfg["families"])
        who = data.assignment(names, cfg["n_clients"])
        counts = {f: who.count(f) for f in names}
        return ref.init_weights(cfg["families"], counts,
                                cfg["series_length"], cfg["n_classes"],
                                jax.random.key(data.sub_seed(self.seed, 5)))

    def _rng(self):
        return jax.random.key(data.sub_seed(self.seed, 6))

    def setup(self) -> None:
        from repro.core import FederationConfig, FederationEngine, Protocol
        from repro.core import runtime
        from repro.data.partition import ClientSplit
        from repro.data.synthetic import FederatedDataset
        from repro.models.zoo import build_zoo

        cfg = self.cfg
        self.data = fed = data.make_federation(cfg, self.seed)
        ds = FederatedDataset(
            "bench", cfg["n_classes"], cfg["series_length"],
            [c.train_x for c in fed.clients], [c.train_y for c in fed.clients],
            fed.ref_x, fed.ref_y, fed.cluster)
        splits = [ClientSplit(c.train_x, c.train_y, c.val_x, c.val_y,
                              c.test_x, c.test_y) for c in fed.clients]
        names = list(cfg["families"])
        proto = cfg["protocol"]
        eng = FederationEngine.build(
            ds, splits, build_zoo(",".join(names), cfg["series_length"],
                                  cfg["n_classes"]),
            None, Protocol("sqmd", rho=proto["rho"], q=proto["q"],
                           k=proto["k"]),
            config=FederationConfig(
                rounds=1, batch_size=cfg["batch_size"],
                local_steps=cfg["local_steps"], uplink=cfg["uplink"],
                downlink=cfg["downlink"]),
            seed=0)
        self.w0 = self._weights()
        for coh in eng.fed.cohorts:
            want = jax.tree.map(lambda a: (a.shape, a.dtype),
                                self.w0[coh.family_name])
            have = jax.tree.map(lambda a: (a.shape, a.dtype), coh.params)
            if want != have:
                raise ValueError(f"family {coh.family_name}: the program's "
                                 f"weights are not the configuration's "
                                 f"shapes: {have} vs {want}")
            coh.params = self.w0[coh.family_name]
        eng.fed.rng = self._rng()
        self.eng = eng
        # the check rounds go through run_round; the cohort step's
        # per-client losses, which the engine drops, are kept on the way
        losses = []
        step = runtime.cohort_step

        def keep_loss(*a, **k):
            out = step(*a, **k)
            losses.append(out[2])
            return out

        runtime.cohort_step = keep_loss
        try:
            for _ in range(self.check_rounds):
                self._round()
                if self.round == 1:
                    self.state1 = {c.family_name: c.opt_state
                                   for c in eng.fed.cohorts}
        finally:
            runtime.cohort_step = step
        self.loss = losses
        self.order = [c.family_name for c in eng.fed.cohorts]
        self.w_end = {c.family_name: c.params for c in eng.fed.cohorts}
        self._block()

    def _round(self) -> None:
        self.eng.run_round(self.round)
        self.round += 1

    def _block(self) -> None:
        jax.block_until_ready([c.params for c in self.eng.fed.cohorts]
                              + [self.eng.fed.targets])

    # -- the window --------------------------------------------------------
    def spans(self):
        eng = self.eng
        return [(eng.clients, "local_round", "local_round"),
                (eng.clients, "collect_messengers", "collect_messengers"),
                (eng.bus, "deliver", "deliver"), (eng.bus, "fire", "fire"),
                (eng.policy, "grade", "grade"),
                (eng.policy, "build_graph", "build_graph"),
                (eng.policy, "emit_targets", "emit_targets")]

    def window(self, seconds: float) -> dict:
        start = self.round
        t0 = time.perf_counter()
        while True:
            self._round()
            if time.perf_counter() - t0 >= seconds:
                break
        self._block()
        wall = time.perf_counter() - t0
        n = self.round - start
        return {"e2e": {"round_ms": 1e3 * wall / n}, "attempted": n,
                "failed": 0,
                "counters": {"rounds": n, "wall_s": wall,
                             "round_flops": flops.sync_round(self.cfg)}}

    # -- the output check --------------------------------------------------
    def release(self) -> None:
        names, n_fam = self.order, len(self.order)
        self.prog_loss = [
            {f: np.asarray(self.loss[r * n_fam + i], np.float32)
             for i, f in enumerate(names)}
            for r in range(self.check_rounds)]
        self.prog_grad = {f: first_gradient(self.cfg["families"][f]
                                            ["optimizer"], self.state1[f])
                          for f in names}
        self.prog_delta = {
            f: [np.asarray(b, np.float32) - np.asarray(a, np.float32)
                for a, b in zip(jax.tree.leaves(self.w0[f]),
                                jax.tree.leaves(self.w_end[f]))]
            for f in names}
        del self.eng, self.loss, self.state1, self.w_end, self.w0

    def check(self):
        out = ref.federation_rounds(self.cfg, self.data, self._weights(),
                                    self._rng(), self.check_rounds)
        return sorted(reference_gaps(self.cfg, out, self.prog_loss,
                                     self.prog_grad,
                                     self.prog_delta).items())


def first_gradient(opt: dict, state):
    """The first step's gradient, by leaf, as the optimizer holds it after
    that step: SGD's momentum buffer is the gradient itself; Adam's first
    moment is (1 - b1) times it."""
    if opt["name"] == "sgd":
        return [np.asarray(a, np.float32)
                for a in jax.tree.leaves(state.momentum)]
    return [np.asarray(a, np.float32) / (1.0 - opt["b1"])
            for a in jax.tree.leaves(state.mu)]


def reference_gaps(cfg: dict, out: dict, prog_loss, prog_grad,
                   prog_delta) -> dict:
    names = list(cfg["families"])
    ref_grad = {f: jax.tree.leaves(out["grads"][f]) for f in names}
    w0 = out["weights0"]
    ref_delta = {f: [b - a for a, b in zip(jax.tree.leaves(w0[f]),
                                           jax.tree.leaves(
                                               out["weights"][-1][f]))]
                 for f in names}
    return compare.training(prog_loss, out["loss"], prog_grad, ref_grad,
                            prog_delta, ref_delta)
