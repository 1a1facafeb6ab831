"""Work counts from shapes: the operations and bytes the algorithm needs,
whatever implements it.

FLOPs count 2 per multiply-accumulate of the matmul-like contractions
(dense layers, convolutions, attention, the SSD state update and read-out,
Eq. 2 and Eq. 5); elementwise work is left out, as model FLOP counts
usually do. Bytes count each float32 operand read once and each result
written once.
"""
from __future__ import annotations

import math
from typing import Tuple

from bench.data import assignment

F32 = 4


# --------------------------------------------------------------------------
# server kernels
# --------------------------------------------------------------------------

def kl_strip(u: int, m: int, r: int, c: int) -> Tuple[float, float]:
    """Eq. 2 strip (U,M): the cross term over R*C per pair; reads both
    log-probability stacks, writes the strip."""
    return 2.0 * u * m * r * c, float((u + m) * r * c * F32 + u * m * F32)


def neighbor_mean(n: int, k: int, r: int, c: int) -> Tuple[float, float]:
    """Eq. 5 with K neighbours per client: K weighted rows of R*C each;
    reads the probabilities and the (N,K) neighbour ids, writes the
    targets."""
    return 2.0 * n * k * r * c, float(2 * n * r * c * F32 + n * k * F32)


def roofline_s(flops: float, nbytes: float, peak: dict) -> Tuple[float,
                                                                 str]:
    """The least time the chip needs, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_b else (t_b, "bytes")


# --------------------------------------------------------------------------
# client families (per sample)
# --------------------------------------------------------------------------

def family_forward(fam: dict, in_dim: int, n_classes: int) -> float:
    kind = fam["kind"]
    if kind == "mlp":
        dims = (in_dim, *fam["hidden"], n_classes)
        return sum(2.0 * a * b for a, b in zip(dims[:-1], dims[1:]))
    if kind == "resnet1d":
        w, length = fam["width"], in_dim
        total = 2.0 * length * 3 * 1 * w                      # stem
        c_in = w
        for stage, n_blocks in enumerate(fam["blocks"]):
            c_out = w * 2 ** stage
            for b in range(n_blocks):
                stride = fam["pool_stride"] if (b == 0 and stage > 0) else 1
                length = math.ceil(length / stride)
                total += 2.0 * length * 3 * c_in * c_out       # w1
                total += 2.0 * length * 3 * c_out * c_out      # w2
                if c_in != c_out:
                    total += 2.0 * length * c_in * c_out       # 1x1 skip
                c_in = c_out
        return total + 2.0 * c_in * n_classes
    s, d = fam["seq_len"], fam["d_model"]
    patch = math.ceil(in_dim / s)
    total = 2.0 * s * patch * d + 2.0 * d * n_classes          # embed, head
    if kind == "transformer":
        h, kv = fam["n_heads"], fam["n_kv_heads"]
        hd = d // h
        pairs = s * (s + 1) / 2                                # causal
        total += 2.0 * s * d * (h + 2 * kv) * hd               # q, k, v
        total += 2.0 * 2 * h * pairs * hd                      # scores, AV
        total += 2.0 * s * h * hd * d                          # out
        return total
    if kind == "ssd":
        di = fam["ssm_expand"] * d
        n, nh = fam["ssm_state"], fam["ssm_heads"]
        width = 2 * di + 2 * n + nh
        total += 2.0 * s * d * width                           # in_proj
        total += 2.0 * s * fam["conv_width"] * (di + 2 * n)    # conv
        total += 2.0 * 2 * s * di * n                          # state, C
        total += 2.0 * s * di * d                              # out_proj
        return total
    raise ValueError(f"unknown family kind {kind!r}")


def sync_round(cfg: dict) -> float:
    """FLOPs of one round of the sync engine with distillation on: every
    client's forward and backward (3x forward) over its local batch and
    the R reference samples, its R messenger forwards, and the server's
    Eq. 2 over all N^2 pairs and Eq. 5."""
    n, r, c = cfg["n_clients"], cfg["ref_size"], cfg["n_classes"]
    total = 0.0
    for name in assignment(list(cfg["families"]), n):
        fam = cfg["families"][name]
        fwd = family_forward(fam, cfg["series_length"], c)
        total += 3.0 * fwd * (cfg["batch_size"] + r) + fwd * r
    k = min(cfg["protocol"]["k"], n - 1)
    return (total + kl_strip(n, n, r, c)[0]
            + neighbor_mean(n, k, r, c)[0])


def server_fire(n: int, u: int, k: int, r: int, c: int) -> float:
    """FLOPs of one delta fire: the (u,N) and (N,u) Eq. 2 strips, Eq. 5."""
    return 2 * kl_strip(u, n, r, c)[0] + neighbor_mean(n, k, r, c)[0]
