"""Feed-forward layers: dense SwiGLU and Mixture-of-Experts.

MoE has three execution paths:
  * ``moe_gshard_forward`` — GShard/Switch-style dispatch-einsum with capacity
    + token dropping. This path has clean GSPMD sharding (experts on the
    ``model`` axis when divisible → expert parallelism with all-to-all) and is
    what the multi-pod dry-run lowers.
  * ``moe_dropless_forward`` — sort-based dropless path using
    ``jax.lax.ragged_dot`` (MegaBlocks-style). Exact active-FLOPs; used on
    CPU smoke/federation paths and as the correctness oracle.
  * ``moe_decode`` — per-token expert-weight gather for single-token decode.

``held_moe_forward`` is Nemotron-H's expert layer (a layer kind of its own,
``"moe"``): DeepSeek-V3's sigmoid router with a score-correction bias and
normalised, scaled top-k weights; relu² experts (up, then down, no gate)
and a relu² shared expert. The layer holds ``cfg.n_held`` of the
``cfg.n_experts`` routed experts, from expert ``first`` on (0 in the
program) — one chip's share under expert parallelism — routes every
token over all of them, and adds only its held experts' part, dropless.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, Params, dense_init, swiglu

HIGHEST = jax.lax.Precision.HIGHEST

MOE_CAPACITY_FACTOR = 1.25


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_dense_ffn(key, cfg: ModelConfig, d_ff: int = 0) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = cfg.param_dtype
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, f), dt),
        "w_up": dense_init(ks[1], (d, f), dt),
        "w_down": dense_init(ks[2], (f, d), dt, fan_in=f),
    }


def init_moe(key, cfg: ModelConfig) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = cfg.param_dtype
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), dt),
        "w_up": dense_init(ks[2], (e, d, f), dt),
        "w_down": dense_init(ks[3], (e, f, d), dt, fan_in=f),
    }
    if cfg.n_shared_experts > 0:
        # shared experts act as one dense FFN of width n_shared * d_ff
        shared_cfg_ff = cfg.n_shared_experts * f
        p["shared"] = init_dense_ffn(ks[4], cfg, d_ff=shared_cfg_ff)
    return p


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------

def dense_ffn(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = swiglu(g, u)
    # row-parallel w_down: bf16 cross-shard reduction (see §Perf)
    return jnp.einsum("bsf,fd->bsd", h.astype(x.dtype), p["w_down"])


# ---------------------------------------------------------------------------
# routing (shared by all MoE paths)
# ---------------------------------------------------------------------------

def route(p: Params, cfg: ModelConfig, x: jnp.ndarray):
    """x (..., D) -> (combine_weights (..., k), expert_idx (..., k), aux_loss)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"])
    k = cfg.moe_top_k
    vals, idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(vals, axis=-1)
    # Switch-style load-balance auxiliary loss
    probs = jax.nn.softmax(logits, axis=-1)                 # (..., E)
    e = cfg.n_experts
    me = jnp.mean(probs.reshape(-1, e), axis=0)
    one_hot = jax.nn.one_hot(idx.reshape(-1), e, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)
    aux = e * jnp.sum(me * ce)
    return weights, idx, aux


# ---------------------------------------------------------------------------
# GShard dispatch path (multi-pod dry-run / pjit path)
# ---------------------------------------------------------------------------

def moe_gshard_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                       capacity_factor: float = MOE_CAPACITY_FACTOR):
    """x (B,S,D). Dispatch/combine einsums with per-(B-row) expert capacity."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    cap = int(max(1, round(s * k / e * capacity_factor)))
    # align capacity to the mesh model-axis (16) so it stays shardable
    cap = -(-cap // 16) * 16

    weights, idx, aux = route(p, cfg, x)                    # (B,S,k)
    # position of each (token, choice) inside its expert's buffer
    oh = jax.nn.one_hot(idx, e, dtype=jnp.int32)            # (B,S,k,E)
    oh_flat = oh.reshape(b, s * k, e)
    pos_in_e = jnp.cumsum(oh_flat, axis=1) * oh_flat - 1    # (B,S*k,E)
    pos_in_e = pos_in_e.reshape(b, s, k, e)
    keep = (pos_in_e < cap) & (oh > 0)                      # drop overflow
    # dispatch (B,S,E,C) one-hot over capacity slots
    cap_oh = jax.nn.one_hot(jnp.where(keep, pos_in_e, -1), cap,
                            dtype=x.dtype)                  # (B,S,k,E,C)
    dispatch = jnp.sum(cap_oh, axis=2)                      # (B,S,E,C)
    combine = jnp.sum(cap_oh * weights[..., None, None].astype(x.dtype),
                      axis=2)                               # (B,S,E,C)

    xe = jnp.einsum("bsec,bsd->becd", dispatch, x)          # (B,E,C,D)
    g = jnp.einsum("becd,edf->becf", xe, p["w_gate"])
    u = jnp.einsum("becd,edf->becf", xe, p["w_up"])
    h = swiglu(g, u)
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    y = jnp.einsum("bsec,becd->bsd", combine, ye)
    if "shared" in p:
        y = y + dense_ffn(p["shared"], x)
    return y, aux


# ---------------------------------------------------------------------------
# dropless sort-based path (CPU smoke / oracle)
# ---------------------------------------------------------------------------

def moe_dropless_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray):
    """Exact dropless MoE via argsort + jax.lax.ragged_dot."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    t = b * s
    xf = x.reshape(t, d)
    weights, idx, aux = route(p, cfg, x)
    wf = weights.reshape(t * k)
    ef = idx.reshape(t * k)
    token_of = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(ef)
    xs = xf[token_of[order]]                                 # (t*k, D)
    group_sizes = jnp.bincount(ef, length=e).astype(jnp.int32)

    g = jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    u = jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    h = swiglu(g, u)
    ys = jax.lax.ragged_dot(h, p["w_down"], group_sizes)     # (t*k, D)

    yw = ys * wf[order][:, None].astype(ys.dtype)
    y = jnp.zeros((t, d), ys.dtype).at[token_of[order]].add(yw)
    y = y.reshape(b, s, d).astype(x.dtype)
    if "shared" in p:
        y = y + dense_ffn(p["shared"], x)
    return y, aux


# ---------------------------------------------------------------------------
# decode path (one token per row)
# ---------------------------------------------------------------------------

def moe_decode(p: Params, cfg: ModelConfig, x: jnp.ndarray):
    """x (B,1,D): gather the k selected experts' weights per row."""
    b, s, d = x.shape
    if s != 1:
        # ValueError (not assert): trace-time guard survives python -O
        raise ValueError(f"moe_decode expects one token per row, got S={s}")
    weights, idx, aux = route(p, cfg, x)                     # (B,1,k)
    idxf = idx[:, 0, :]                                      # (B,k)
    wg = p["w_gate"][idxf]                                   # (B,k,D,F)
    wu = p["w_up"][idxf]
    wd = p["w_down"][idxf]
    xe = x[:, 0, :]                                          # (B,D)
    g = jnp.einsum("bd,bkdf->bkf", xe, wg)
    u = jnp.einsum("bd,bkdf->bkf", xe, wu)
    h = swiglu(g, u)
    ye = jnp.einsum("bkf,bkfd->bkd", h, wd,
                    preferred_element_type=jnp.float32)
    y = jnp.einsum("bkd,bk->bd", ye,
                   weights[:, 0, :].astype(ye.dtype))[:, None, :].astype(x.dtype)
    if "shared" in p:
        y = y + dense_ffn(p["shared"], x)
    return y, aux


def moe_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                path: str = "gshard"):
    if path == "gshard":
        return moe_gshard_forward(p, cfg, x)
    if path == "dropless":
        return moe_dropless_forward(p, cfg, x)
    raise ValueError(f"unknown moe path {path!r}")


# ---------------------------------------------------------------------------
# Nemotron-H expert layer: DeepSeek-V3 routing, relu² experts, held share
# ---------------------------------------------------------------------------

def relu2(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.square(jax.nn.relu(x))


def init_held_moe(key, cfg: ModelConfig) -> Params:
    d, f, fs = cfg.d_model, cfg.d_ff, cfg.shared_d_ff
    held = cfg.n_held
    dt = cfg.param_dtype
    ks = jax.random.split(key, 5)
    return {
        "router": dense_init(ks[0], (d, cfg.n_experts), jnp.float32),
        # e_score_correction_bias: it only shifts the choice, so it takes
        # no gradient and the optimizer leaves it at its value
        "router_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        "w_up": dense_init(ks[1], (held, d, f), dt, fan_in=d),
        "w_down": dense_init(ks[2], (held, f, d), dt, fan_in=f),
        "shared_up": dense_init(ks[3], (d, fs), dt),
        "shared_down": dense_init(ks[4], (fs, d), dt, fan_in=fs),
    }


def sigmoid_route(p: Params, cfg: ModelConfig, xf: jnp.ndarray):
    """DeepSeek-V3 routing of tokens xf (T, D): scores s = sigmoid(x W_r)
    (float32, HIGHEST precision, so the choice matches a float32
    reference except on true ties); the top k of s + bias; weights
    s_i / sum_chosen s * routed_scale. Returns (ids (T,k), weights (T,k))."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"],
                        precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["router_bias"]), cfg.moe_top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg.routed_scale
    return ids, w


# tokens a block of the expert layer: a block's held-expert activations
# are block * n_held * d_ff floats, so bounding the block bounds the
# layer's buffers (each block is rematerialised in the backward pass)
MOE_BLOCK = 4096


def _held_block(p: Params, cfg: ModelConfig, xf: jnp.ndarray,
                real: jnp.ndarray, first: int):
    """One block of tokens xf (T, D), ``real`` (T,) marking the tokens
    that are not padding: (y (T, D), counts (n_held,))."""
    held = cfg.n_held
    ids, w = sigmoid_route(p, cfg, xf)
    mine = ((ids - first)[..., None] == jnp.arange(held)) \
        & real[:, None, None]                                # (T, k, held)
    gate = jnp.sum(jnp.where(mine, w[..., None], 0.0), axis=1)   # (T, held)
    counts = jnp.sum(mine, axis=(0, 1), dtype=jnp.int32)
    h = relu2(jnp.einsum("td,edf->tef", xf, p["w_up"]))
    routed = jnp.einsum("tef,efd->td", h * gate[..., None].astype(h.dtype),
                        p["w_down"])
    shared = relu2(xf @ p["shared_up"]) @ p["shared_down"]
    return routed + shared, counts


def held_moe_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                     block: int = MOE_BLOCK, first: int = 0):
    """x (B,S,D) -> (y (B,S,D), counts (n_held,) int32): the shared expert
    plus sum over chosen ∩ held experts i of w_i relu²(x U_i) D_i.

    Every held expert runs on every token of a block, weighed by its gate
    (nought where the token did not choose it): dropless, with the same
    work whatever the routing, as dense matmuls on the MXU. Tokens go in
    blocks of at most ``block``. ``counts`` is each held expert's number
    of token choices. The layer holds experts ``first`` to ``first +
    n_held - 1``."""
    b, s, d = x.shape
    t = b * s
    nb = -(-t // block)
    tb = -(-t // nb)
    xf = jnp.pad(x.reshape(t, d), ((0, nb * tb - t), (0, 0)))
    real = jnp.arange(nb * tb) < t
    y, counts = jax.lax.map(
        jax.checkpoint(lambda a: _held_block(p, cfg, *a, first)),
        (xf.reshape(nb, tb, d), real.reshape(nb, tb)))
    y = y.reshape(nb * tb, d)[:t].reshape(b, s, d)
    return y.astype(x.dtype), jnp.sum(counts, axis=0)
