"""Quickstart: the SQMD protocol in ~50 lines with the public API.

Builds a 28-client heterogeneous federation (3 MLP families) on a synthetic
apnea-like dataset, trains 25 rounds with the SQMD policy through the
``FederationEngine``, and prints the accuracy plus the REAL collaboration
graph the server last built.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (FederationConfig, FederationEngine, graph_stats,
                        selection_matrix, sqmd)
from repro.data import make_splits, pad_like
from repro.models.mlp import hetero_mlp_zoo


def main():
    # 1. data: 28 clients with private non-IID shards + a shared reference
    #    set whose labels only the server holds (paper Def. 1)
    ds = pad_like(samples_per_client=60, ref_size=120)
    splits = make_splits(ds, seed=0, label_noise=0.3)

    # 2. heterogeneous client models: three capacity tiers, mirroring the
    #    paper's ResNet8/20/50 mix — no parameter averaging is possible
    zoo = hetero_mlp_zoo(ds.feature_len, ds.n_classes)
    assignment = [list(zoo)[i % 3] for i in range(ds.n_clients)]

    # 3. the policy: quality top-Q filter, similarity top-K neighbors,
    #    distill with weight rho (paper Eq. 6). Any registered policy name
    #    or ServerPolicy instance drops in here unchanged.
    engine = FederationEngine.build(
        ds, splits, zoo, assignment, sqmd(q=12, k=6, rho=0.8),
        config=FederationConfig(rounds=25, batch_size=16, eval_every=5,
                                verbose=True),
        seed=1)
    hist = engine.fit(splits)

    print(f"\nfinal mean test accuracy: {hist.mean_acc[-1]:.4f}")

    # 4. inspect the dynamic collaboration graph the server learned — the
    #    engine keeps the policy's actual last-built graph (true top-Q
    #    candidate pool included, no placeholder reconstruction)
    print("collaboration graph:", graph_stats(engine.last_graph))

    # how well did similarity recover the ground-truth clusters?
    w = np.asarray(selection_matrix(engine.last_graph))
    cl = ds.client_cluster
    hit = [np.mean(cl[np.where(w[i] > 0)[0]] == cl[i])
           for i in range(ds.n_clients)]
    print(f"neighbor/cluster agreement: {np.mean(hit):.2f} "
          f"(random would be ~{np.mean([np.mean(cl == c) for c in cl]):.2f})")


if __name__ == "__main__":
    main()
