"""Plain reference of the ``nemotron-h`` client family: straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
with no kernels, no chunking and no grouped matmul. It reads the same
parameter tree as ``repro.models.zoo.nemotron_h_family`` and follows
Nemotron-H (arXiv:2504.03624; Nemotron-3-Nano's ``config.json``):

  * every layer is ``x + op(RMSNorm(x))``, ``op`` one of the Mamba-2 mixer
    (``M``), the expert layer (``E``) and GQA attention (``*``);
  * Mamba-2: in_proj to [z, x, B, C, dt]; a depthwise causal conv with
    bias and SiLU over [x, B, C]; dt = softplus(dt + dt_bias); the SSD as
    the sequential recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t,
    y_t = C_t · h_t + D x_t, B and C read by head group; RMSNorm of
    y * silu(z) over groups of d_inner / n_groups channels; out_proj;
  * the expert layer: s = sigmoid(x W_r); the top k of s + b; weights
    s_i / Σ_chosen s · routed_scale; relu² experts (up, down) and a relu²
    shared expert, written out per token;
  * attention: softmax over an explicit causal mask, 32 query heads
    sharing 2 K/V heads, scaled by 1/sqrt(head_dim).

Departures from the published model, the same as the program's:
  * the front end: a linear embedding of patch tokens of the series in
    place of the token embedding;
  * the head: the final RMSNorm, a mean over tokens and a class head in
    place of the LM head;
  * the held share: each expert layer holds ``experts_held`` experts from
    expert ``first`` on (one chip's share under expert parallelism) and
    adds only their part; the router still scores all ``n_experts``;
  * the score-correction bias ``b`` is a frozen buffer (DeepSeek-V3's
    load-based update of it is not implemented);
  * attention applies no positional encoding (Nemotron-H's attention
    layers use none; not checked against a ``nemotron_h`` module, which
    the installed ``transformers`` lacks).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig


def _rms(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba(p, cfg: ModelConfig, x):
    """x (B,S,D) -> (B,S,D), by the sequential recurrence."""
    b, s, _ = x.shape
    h, g, n = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state
    di = cfg.d_inner
    hp = di // h
    proj = x @ p["w_in"]
    z = proj[..., :di]
    xbc = proj[..., di:2 * di + 2 * g * n]
    dt = jax.nn.softplus(proj[..., 2 * di + 2 * g * n:] + p["dt_bias"])
    width = p["conv_w"].shape[0]
    past = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(past[:, i:i + s] * p["conv_w"][i] for i in range(width))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xh = xbc[..., :di].reshape(b, s, h, hp)
    grp = jnp.arange(h) // (h // g)                  # each head's group
    bm = xbc[..., di:di + g * n].reshape(b, s, g, n)[:, :, grp]   # (B,S,H,N)
    cm = xbc[..., di + g * n:].reshape(b, s, g, n)[:, :, grp]
    a = -jnp.exp(p["a_log"])

    def step(state, t):
        dt_t, x_t, b_t, c_t = t
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, ys = jax.lax.scan(step, jnp.zeros((b, h, hp, n), x.dtype),
                         (jnp.moveaxis(dt, 1, 0), jnp.moveaxis(xh, 1, 0),
                          jnp.moveaxis(bm, 1, 0), jnp.moveaxis(cm, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1) + p["d_skip"][:, None] * xh
    y = (y.reshape(b, s, di) * jax.nn.silu(z)).reshape(b, s, g, di // g)
    y = _rms(1.0, y, cfg.norm_eps).reshape(b, s, di) * p["norm_scale"]
    return y @ p["w_out"]


def experts(p, cfg: ModelConfig, x, first: int = 0):
    """x (B,S,D) -> (y (B,S,D), counts (held,)): the shared expert plus
    the part of the held experts, ``first`` on, per token."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    scores = jax.nn.sigmoid(xf @ p["router"])                   # (T,E)
    _, ids = jax.lax.top_k(scores + p["router_bias"], cfg.moe_top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg.routed_scale
    held = p["w_up"].shape[0]
    # gate[t, i]: token t's weight on held expert i, zero unless chosen
    mine = ids[:, :, None] == first + jnp.arange(held)
    gate = jnp.sum(jnp.where(mine, w[:, :, None], 0.0), axis=1)  # (T,held)
    out = jnp.stack([_relu2(xf @ p["w_up"][i]) @ p["w_down"][i]
                     for i in range(held)], axis=1)              # (T,held,D)
    y = jnp.einsum("te,ted->td", gate, out)
    y = y + _relu2(xf @ p["shared_up"]) @ p["shared_down"]
    return y.reshape(b, s, d), jnp.sum(mine, axis=(0, 1))


def attention(p, cfg: ModelConfig, x):
    s = x.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bshk,bthk->bhst", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bshk,hkd->bsd",
                      jnp.einsum("bhst,bthk->bshk", probs, v), p["wo"])


def layer(p, cfg: ModelConfig, kind: str, x):
    """One single-op block; returns (x, counts or None)."""
    h = _rms(p["norm1"]["scale"], x, cfg.norm_eps)
    if kind == "moe":
        y, counts = experts(p["mixer"], cfg, h)
        return x + y, counts
    op = mamba if kind == "ssd" else attention
    return x + op(p["mixer"], cfg, h), None


def forward(p, cfg: ModelConfig, x, patch: int):
    """Logits (B, C) of series x (B, L), and the expert layers'
    token-choice counts per held expert (n_expert_layers, held)."""
    with jax.default_matmul_precision("highest"):
        seq = -(-x.shape[1] // patch)
        xp = jnp.pad(x, ((0, 0), (0, seq * patch - x.shape[1])))
        h = xp.reshape(x.shape[0], seq, patch) @ p["embed_w"] + p["embed_b"]
        counts = []
        for g in range(cfg.n_groups):
            for i, kind in enumerate(cfg.layer_pattern):
                lp = jax.tree.map(lambda a: a[g],
                                  p["stack"]["groups"][f"pos{i}"])
                h, c = layer(lp, cfg, kind, h)
                if c is not None:
                    counts.append(c)
        h = jnp.mean(_rms(p["final_norm"]["scale"], h, cfg.norm_eps), axis=1)
        return h @ p["head_w"] + p["head_b"], jnp.stack(counts)
