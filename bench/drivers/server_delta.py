"""Driver: a closed loop of client uploads into the program's
``ServerBus``, each delivery firing a delta update of the graph.

Set-up makes every client's messenger and ``pool`` further stacks of
fresh ones on the device from the seed, uploads the whole population once
(the full rebuild that makes the divergence cache exact) and warms the
delta path with ``warm_rounds`` uploads. Round i then delivers the
messengers of stack ``i % pool`` for the next ``uploads_per_round``
clients of the seeded upload order. No client trains.

The output check rebuilds the final repository from the seed and the
rounds run, and compares the program's repository, grades, candidate
pool, divergence cache, neighbour choice and targets with the reference.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, flops, ref


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.u = int(traffic["uploads_per_round"])
        self.n_pool = int(traffic["pool"])
        self.round = 0

    def setup(self) -> None:
        from repro.core import Protocol, init_server
        from repro.core.engine import Federation
        from repro.core.policies import as_policy
        from repro.core.runtime import ServerBus

        cfg = self.cfg
        n, r, c = cfg["n_clients"], cfg["ref_size"], cfg["n_classes"]
        proto = cfg["protocol"]
        stacks, labels = data.make_messengers(cfg, self.seed,
                                              1 + self.n_pool)
        first = stacks[0]
        self.pool = [stacks[1 + i] for i in range(self.n_pool)]
        del stacks
        policy = as_policy(Protocol("sqmd", rho=proto["rho"], q=proto["q"],
                                    k=proto["k"]))
        fed = Federation(cohorts=[], server=init_server(n, r, c),
                         protocol=policy.protocol,
                         ref_x=jnp.zeros((r, 1), jnp.float32), ref_y=labels,
                         optimizer=None, n_clients=n,
                         uplink=cfg["uplink"], downlink=cfg["downlink"])
        self.bus = ServerBus(fed, policy, trigger="every-upload",
                             delta=cfg["delta_graph"],
                             selection=cfg["selection"])
        self.fed, self.policy = fed, policy
        self.bus.deliver(0.0, first, np.ones(n, bool))
        del first
        self.order = data.upload_rows(self.seed, n, self.u)
        self.last = np.full(n, -1, np.int64)     # last round each row sent
        for _ in range(int(self.traffic["warm_rounds"])):
            self._round()
        self._block()

    def _round(self) -> None:
        rows = next(self.order)
        mask = np.zeros(self.cfg["n_clients"], bool)
        mask[rows] = True
        i = self.round
        self.bus.deliver(float(i + 1), self.pool[i % self.n_pool], mask)
        self.last[rows] = i
        self.round += 1

    def _block(self) -> None:
        jax.block_until_ready((self.fed.targets, self.fed.server))

    # -- the window --------------------------------------------------------
    def spans(self):
        bus, pol = self.bus, self.policy
        return [(bus, "deliver", "deliver"), (bus, "fire", "fire"),
                (pol, "grade", "grade"),
                (pol, "build_graph_delta", "build_graph_delta"),
                (pol, "emit_targets", "emit_targets")]

    def window(self, seconds: float) -> dict:
        start, fired = self.round, self.bus.n_triggers
        t0 = time.perf_counter()
        while True:
            self._round()
            if time.perf_counter() - t0 >= seconds:
                break
        self._block()
        wall = time.perf_counter() - t0
        n = self.round - start
        cfg = self.cfg
        nn, r, c = cfg["n_clients"], cfg["ref_size"], cfg["n_classes"]
        k = cfg["protocol"]["k"]
        return {"e2e": {"fire_ms": 1e3 * wall / n}, "attempted": n,
                "failed": n - (self.bus.n_triggers - fired),
                "counters": {
                    "fires": n, "wall_s": wall,
                    "fire_flops": flops.server_fire(nn, self.u, k, r, c),
                    "strip_work": flops.kl_strip(self.u, nn, r, c),
                    "nm_work": flops.neighbor_mean(nn, k, r, c)}}

    # -- the output check --------------------------------------------------
    def release(self) -> None:
        """Keep what the check compares; free the rest of the program."""
        s, g = self.fed.server, self.bus.last_graph
        self.out = {"repo": s.repo_logp, "grade": s.quality,
                    "div": s.div_cache,
                    "cand": np.asarray(g.candidates, bool),
                    "nbrs": np.asarray(g.neighbors),
                    "targets": self.fed.targets}
        del self.bus, self.fed, self.policy, self.pool

    def check(self):
        cfg, o = self.cfg, self.out
        n = cfg["n_clients"]
        stacks, labels = data.make_messengers(cfg, self.seed,
                                              1 + self.n_pool)
        src = np.where(self.last < 0, 0, 1 + self.last % self.n_pool)
        repo = stacks[jnp.asarray(src), jnp.arange(n)]
        del stacks
        return sorted(server_gaps(repo, labels, o,
                                  cfg["protocol"]["q"], cfg["protocol"]["k"],
                                  int(self.traffic["check_block"])).items())


@jax.jit
def _block_gaps(div_ref, div_prog, targets, want, nbrs, pool, rows):
    """One block of rows: the cache's largest gap to the reference Eq. 2
    (over the larger of the entry and its row's median), the targets'
    largest relative gap, and how far each row's worst chosen neighbour
    lies above its K-th best pool member by the reference divergences
    (inf where a choice is no other pool member or is chosen twice)."""
    scale = jnp.maximum(jnp.abs(div_ref),
                        jnp.median(div_ref, axis=1, keepdims=True))
    div_gap = jnp.max(jnp.abs(div_prog - div_ref) / scale)
    tgt_gap = jnp.max(jnp.abs(targets - want) / want)
    n, k = div_ref.shape[1], nbrs.shape[1]
    other = pool[None, :] & (jnp.arange(n)[None, :] != rows[:, None])
    d = jnp.where(other, div_ref, jnp.inf)
    kth = -jax.lax.top_k(-d, k)[0][:, -1]
    chosen = jnp.take_along_axis(d, jnp.clip(nbrs, 0, n - 1), axis=1)
    chosen = jnp.where(nbrs >= 0, chosen, jnp.inf)
    s = jnp.sort(nbrs, axis=1)
    twice = jnp.any(s[:, 1:] == s[:, :-1], axis=1)
    worst = jnp.where(twice, jnp.inf, jnp.max(chosen, axis=1))
    return div_gap, tgt_gap, jnp.max(worst / kth - 1.0)


def server_gaps(repo, labels, prog: dict, q: int, k: int,
                block: int) -> dict:
    """The program's server state ``prog`` (the keys of ``release``)
    against the reference over repository ``repo`` (N,R,C):

      * ``repo``: largest gap of the merged repository (exact: 0);
      * ``grade``: largest relative gap of the Eq. 1 grades;
      * ``pool``: how many clients are in one of the program's and the
        reference's Def. 3 top-Q pools and not in the other (exact: 0);
      * ``div``: largest gap of the cached Eq. 2 divergences after the
        delta updates, over the larger of the entry and its row's median;
      * ``nbrs``: largest excess, over all clients, of the reference
        divergence to the worst neighbour the program chose over that to
        the K-th best pool member (0 where it chose a true top K);
      * ``targets``: largest relative gap of the Eq. 5 targets to the mean
        of the reference probabilities over the neighbours the program
        chose.
    """
    n = repo.shape[0]
    g_ref = np.asarray(ref.grades(repo, labels), np.float64)
    g_prog = np.asarray(prog["grade"], np.float64)
    cand_ref = ref.pool(g_ref, np.ones(n, bool), q)
    out = {"repo": float(jnp.max(jnp.abs(prog["repo"] - repo))),
           "grade": float(np.max(np.abs(g_prog - g_ref) / np.abs(g_ref))),
           "pool": float(np.sum(prog["cand"] != cand_ref))}
    probs = jnp.exp(repo)
    pool = jnp.asarray(cand_ref)
    gaps = np.zeros(3)
    for i in range(0, n, block):
        nb = jnp.asarray(prog["nbrs"][i:i + block])
        got = _block_gaps(ref.divergence(repo[i:i + block], repo),
                          prog["div"][i:i + block],
                          prog["targets"][i:i + block],
                          ref.neighbor_mean(probs, nb), nb, pool,
                          jnp.arange(i, i + nb.shape[0]))
        gaps = np.maximum(gaps, np.asarray(got, np.float64))
    out["div"], out["targets"], out["nbrs"] = (float(g) for g in gaps)
    return out
