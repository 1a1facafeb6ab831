"""Work counts of the ``sc-nemotron3-nano`` federation's round, from shapes
and from the program's token-choice counter, as ``bench/flops.py`` counts
them: 2 FLOPs a multiply-accumulate of the matmul-like contractions
(projections, the conv, the SSD's state update and read-out, attention,
router, experts, head), elementwise work left out, and the backward at
twice the forward (rematerialised forwards are not counted).

The routed experts' work is counted from how many token choices the held
experts took, never from an assumed balance: the counter covers the
training step's forwards (local batches and the reference set); the
messengers' forward over the reference set takes its share of them.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

from bench import flops
from bench.ref_nemotron_h import FAMILY, who


def _pattern(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def hybrid_token(cfg: dict) -> float:
    """Forward FLOPs a token of every layer but the routed experts'."""
    d = cfg["hidden_size"]
    s = math.ceil(cfg["series_length"] / cfg["patch"])
    total = 2.0 * cfg["patch"] * d                             # embed
    for kind in _pattern(cfg):
        if kind == "M":
            h, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
            di, gn = h * hp, cfg["n_groups"] * cfg["ssm_state_size"]
            total += 2.0 * d * (2 * di + 2 * gn + h)           # in_proj
            total += 2.0 * cfg["conv_kernel"] * (di + 2 * gn)  # conv
            total += 2.0 * 2 * di * cfg["ssm_state_size"]      # state, C
            total += 2.0 * di * d                              # out_proj
        elif kind == "E":
            total += 2.0 * d * cfg["router_experts"]           # router
            total += 2.0 * 2 * d * cfg[
                "moe_shared_expert_intermediate_size"]         # shared
        else:
            h, kv, hd = (cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"])
            total += 2.0 * d * (h + 2 * kv) * hd               # q, k, v
            total += 2.0 * 2 * h * hd * (s + 1) / 2            # causal
            total += 2.0 * h * hd * d                          # out
    return total


def routed_choice(cfg: dict) -> float:
    """Forward FLOPs of one token choice of a held expert (up, down)."""
    return 2.0 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def sync_round(cfg: dict, choices: Optional[Sequence[float]] = None
               ) -> float:
    """FLOPs of one round with distillation on: every client's forward
    and backward over its batch and the reference set, its messengers,
    the server's Eq. 2 and Eq. 5. ``choices`` is the held experts'
    token choices a round in the training step of the hybrid clients,
    summed over layers (None counts no routed work)."""
    n, r, c = cfg["n_clients"], cfg["ref_size"], cfg["n_classes"]
    b = cfg["batch_size"]
    s = math.ceil(cfg["series_length"] / cfg["patch"])
    total = 0.0
    for name in who(cfg):
        if name == FAMILY:
            per_sample = hybrid_token(cfg) * s + 2.0 * cfg["hidden_size"] * c
            total += 3.0 * per_sample * (b + r) + per_sample * r
            continue
        fwd = flops.family_forward(cfg["families"][name],
                                   cfg["series_length"], c)
        total += 3.0 * fwd * (b + r) + fwd * r
    if choices is not None:
        step = float(sum(choices)) * routed_choice(cfg)
        total += 3.0 * step + step * r / (b + r)
    k = min(cfg["protocol"]["k"], n - 1)
    return (total + flops.kl_strip(n, n, r, c)[0]
            + flops.neighbor_mean(n, k, r, c)[0])
