"""Work counts: kernels against hand counts, families against XLA's own
count of the reference forward compiled for the CPU."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import flops, ref

CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                      "sc.json")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_strip_counts_by_hand():
    # (256, 16384) strip at R=240, C=3: 2*256*16384*720 FLOPs; reads
    # (256+16384)*720 floats, writes 256*16384
    f, b = flops.kl_strip(256, 16384, 240, 3)
    assert f == 6_039_797_760
    assert b == (256 + 16384) * 720 * 4 + 256 * 16384 * 4
    t, bound = flops.roofline_s(f, b, PEAK)
    assert bound == "bytes" and t == pytest.approx(b / 819e9)


def test_neighbor_mean_is_counted_k_sparse():
    # K=8 neighbours, not the dense N x N product
    f, b = flops.neighbor_mean(16384, 8, 240, 3)
    assert f == 2 * 16384 * 8 * 720
    assert b == 2 * 16384 * 720 * 4 + 16384 * 8 * 4
    assert flops.roofline_s(f, b, PEAK)[1] == "bytes"


def test_compute_bound_when_work_outruns_bytes():
    t, bound = flops.roofline_s(197e12, 1.0, PEAK)
    assert bound == "compute" and t == pytest.approx(1.0)


def test_server_fire_counts_two_strips_and_eq5():
    n, u, k, r, c = 16384, 256, 8, 240, 3
    want = 2 * 2 * u * n * r * c + 2 * n * k * r * c
    assert flops.server_fire(n, u, k, r, c) == want


def test_mlp_count_by_hand():
    fam = {"kind": "mlp", "hidden": [32]}
    assert flops.family_forward(fam, 64, 3) == 2 * (64 * 32 + 32 * 3)


# XLA's count also holds the elementwise work the matmul count leaves
# out (norms, softmax, activations, rope, the SSD gates), so the hand
# count is at most XLA's, and at least this share of it at these widths.
LEAST_SHARE = {"mlp-s": 0.95, "resnet": 0.75, "transformer": 0.75,
               "ssm": 0.65}


@pytest.mark.parametrize("name", sorted(LEAST_SHARE))
def test_family_forward_against_xla_cost_analysis(name):
    with open(CONFIG) as f:
        cfg = json.load(f)
    fam = cfg["families"][name]
    length, c = cfg["series_length"], cfg["n_classes"]
    w = ref.init_weights({name: fam}, {name: 1}, length, c,
                         jax.random.key(0))[name]
    p = jax.tree.map(lambda a: a[0], w)
    x = jnp.zeros((16, length))
    compiled = jax.jit(lambda p, x: ref.forward(fam, p, x)).lower(
        p, x).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    mine = 16 * flops.family_forward(fam, length, c)
    assert LEAST_SHARE[name] * cost["flops"] <= mine <= cost["flops"]


def test_sync_round_counts_every_client():
    with open(CONFIG) as f:
        cfg = json.load(f)
    total = flops.sync_round(cfg)
    fams = list(cfg["families"].values())
    per = sum(flops.family_forward(fams[i % 4], 3000, 3) * (3 * (16 + 240)
                                                            + 240)
              for i in range(32))
    server = 2 * 32 * 32 * 720 + 2 * 32 * 8 * 720
    assert total == pytest.approx(per + server)
