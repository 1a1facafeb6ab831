"""Inputs made from the seed: the Sleep-Cassette-like federation's data and
the server cell's messengers.

These generators belong to the benchmark, so no change to the program can
move them. The federation data follows the statistics of the program's
``repro.data.sc_like`` (clustered sub-populations whose waveform patterns
conflict across clusters, Dirichlet class skew, a cluster-balanced
reference set) and its 8:1:1 split with uniform label noise, drawn here
from the run's own seed.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np


def sub_seed(seed: int, tag: int) -> int:
    """A 31-bit seed for stream ``tag`` of a run seeded with ``seed`` (any
    whole number up to a little over 2**31)."""
    ss = np.random.SeedSequence([int(seed) & 0xFFFFFFFFFFFF, int(tag)])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


# --------------------------------------------------------------------------
# the federation: clustered series, per-client splits
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ClientData:
    train_x: np.ndarray
    train_y: np.ndarray
    val_x: np.ndarray
    val_y: np.ndarray
    test_x: np.ndarray
    test_y: np.ndarray


@dataclasses.dataclass
class Federation:
    n_classes: int
    length: int
    clients: List[ClientData]
    ref_x: np.ndarray          # (R, L) float32
    ref_y: np.ndarray          # (R,) int32
    cluster: np.ndarray        # (N,) latent cluster of each client


def _series(rng, n: int, length: int, cls: int, cluster: int) -> np.ndarray:
    """Waveform pattern (cls + cluster): adjacent clusters reuse each
    other's patterns under other labels, so only similar clients help."""
    t = np.linspace(0, 4 * np.pi, length)[None, :]
    freq = 1.0 + (cls + cluster) * 0.7
    phase = rng.uniform(0, 2 * np.pi, (n, 1))
    x = (np.sin(freq * t + phase) + 0.3 * np.sin(2.3 * freq * t + 1.7 * phase)
         + rng.normal(0, 0.8, (n, length)))
    return x.astype(np.float32)


def _split(rng, x, y, ratio) -> ClientData:
    m = len(y)
    perm = rng.permutation(m)
    total = sum(ratio)
    n_tr, n_va = m * ratio[0] // total, m * ratio[1] // total
    tr, va, te = perm[:n_tr], perm[n_tr:n_tr + n_va], perm[n_tr + n_va:]
    return ClientData(x[tr], y[tr], x[va], y[va], x[te], y[te])


def make_federation(cfg: dict, seed: int) -> Federation:
    """Every client's shard and the reference set, from ``seed``. The
    sizes are the configuration's; only the values depend on the seed."""
    rng = np.random.default_rng(sub_seed(seed, 1))
    n, c, k = cfg["n_clients"], cfg["n_classes"], cfg["n_clusters"]
    length, m = cfg["series_length"], cfg["samples_per_client"]
    cluster = np.arange(n) % k
    rng.shuffle(cluster)
    clients = []
    for i in range(n):
        alpha = np.ones(c)
        alpha[cluster[i] % c] += cfg["class_skew"]
        ys = rng.choice(c, m, p=rng.dirichlet(alpha))
        xs = np.zeros((m, length), np.float32)
        for cls in range(c):
            hit = ys == cls
            xs[hit] = _series(rng, int(hit.sum()), length, cls, cluster[i])
        part = _split(rng, xs, ys.astype(np.int32), cfg["split"])
        flip = rng.random(len(part.train_y)) < cfg["label_noise"]
        part.train_y = part.train_y.copy()
        part.train_y[flip] = rng.integers(0, c, int(flip.sum()))
        clients.append(part)
    per = cfg["ref_size"] // (c * k)
    rx, ry = [], []
    for cl in range(k):
        for cls in range(c):
            rx.append(_series(rng, per, length, cls, cl))
            ry.append(np.full(per, cls, np.int32))
    perm = rng.permutation(per * c * k)
    return Federation(c, length, clients, np.concatenate(rx)[perm],
                      np.concatenate(ry)[perm], cluster)


def assignment(families, n_clients: int) -> List[str]:
    """Round-robin family assignment: client i runs families[i % F]."""
    return [families[i % len(families)] for i in range(n_clients)]


# --------------------------------------------------------------------------
# the server cell: clustered log-probability messengers, on the device
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "r", "c", "clusters",
                                             "stacks"))
def _messenger_stacks(key, n: int, r: int, c: int, clusters: int,
                      stacks: int, base_scale, client_scale):
    """``stacks`` (N,R,C) log-probability stacks: each client draws around
    its latent cluster's logits, freshly for every stack."""
    k_base, k_cl, k_noise = jax.random.split(key, 3)
    base = jax.random.normal(k_base, (clusters, r, c)) * base_scale
    member = jax.random.permutation(k_cl, jnp.arange(n) % clusters)
    noise = jax.random.normal(k_noise, (stacks, n, r, c)) * client_scale
    return jax.nn.log_softmax(base[member][None] + noise, axis=-1)


def make_messengers(cfg: dict, seed: int, stacks: int):
    """(stacks, N, R, C) float32 log-probability messengers and the
    server's (R,) reference labels, made on the device in one call."""
    key = jax.random.key(sub_seed(seed, 2))
    lp = _messenger_stacks(key, cfg["n_clients"], cfg["ref_size"],
                           cfg["n_classes"], cfg["n_clusters"], stacks,
                           cfg["cluster_logit_scale"],
                           cfg["client_logit_scale"])
    labels = jax.random.randint(jax.random.key(sub_seed(seed, 3)),
                                (cfg["ref_size"],), 0, cfg["n_classes"])
    return lp, labels


def upload_rows(seed: int, n_clients: int, per_round: int):
    """Row sets of successive upload rounds: each round ``per_round``
    distinct clients, uniform over the population (consecutive rounds
    walk through seeded permutations, so every client uploads once per
    ``n_clients / per_round`` rounds)."""
    rng = np.random.default_rng(sub_seed(seed, 4))
    per_perm = n_clients // per_round
    while True:
        perm = rng.permutation(n_clients).astype(np.int32)
        for j in range(per_perm):
            yield perm[j * per_round:(j + 1) * per_round]
