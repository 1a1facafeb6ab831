"""Attention mixers: GQA (global + sliding-window) and MLA (DeepSeek-V2).

Two execution paths per mixer:
  * full-sequence (train / prefill) — chunked online-softmax attention
    (flash-style ``lax.scan`` over KV blocks) so 32k-token prefill never
    materializes an (S, S) score matrix;
  * single-token decode against a cache (full KV, ring-buffer window, or MLA
    compressed c_kv/k_rope with the absorbed-matmul trick).

Shapes: x (B, S, D); q (B, S, H, hd); k/v (B, S, KV, hd).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (ModelConfig, NEG_INF, Params, apply_rope,
                                 dense_init)

# KV-block size for the chunked online-softmax path.
KV_CHUNK = 1024
# Sequences at or below this use the plain masked-einsum path (cheaper HLO).
# §Perf note (qwen2 iteration 2, REFUTED): routing 4k training through the
# chunked path cut peak temp 67.9->54.2 GB but RAISED modeled HBM traffic
# 1.6e13->3.2e13 B (the scan carry round-trips per chunk) — in pure JAX the
# online-softmax accumulator lives in HBM, not VMEM; that residency is a
# Pallas-kernel property. Kept at 4096; small-arch replication is fixed by
# the pure-DP sharding policy instead (see repro/sharding.py).
DIRECT_ATTN_MAX_SEQ = 4096


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.param_dtype
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), dt),
        "wk": dense_init(ks[1], (d, kv, hd), dt),
        "wv": dense_init(ks[2], (d, kv, hd), dt),
        "wo": dense_init(ks[3], (h, hd, d), dt, fan_in=h * hd),
    }
    if cfg.qkv_bias:  # qwen2-style
        p["bq"] = jnp.zeros((h, hd), dt)
        p["bk"] = jnp.zeros((kv, hd), dt)
        p["bv"] = jnp.zeros((kv, hd), dt)
    return p


def init_mla(key, cfg: ModelConfig) -> Params:
    """DeepSeek-V2 Multi-head Latent Attention parameters."""
    d, h = cfg.d_model, cfg.n_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    hd, rh = cfg.hd, cfg.rope_head_dim
    vh = cfg.v_head_dim or hd
    dt = cfg.param_dtype
    ks = jax.random.split(key, 7)
    p = {
        # joint KV down-projection: d -> (r  compressed) + (rh shared rope key)
        "w_dkv": dense_init(ks[0], (d, r + rh), dt),
        # up-projections from the compressed latent
        "w_uk": dense_init(ks[1], (r, h, hd), dt, fan_in=r),
        "w_uv": dense_init(ks[2], (r, h, vh), dt, fan_in=r),
        "wo": dense_init(ks[3], (h, vh, d), dt, fan_in=h * vh),
    }
    if qr > 0:
        p["w_dq"] = dense_init(ks[4], (d, qr), dt)
        p["w_uq"] = dense_init(ks[5], (qr, h, hd + rh), dt, fan_in=qr)
    else:
        p["wq"] = dense_init(ks[6], (d, h, hd + rh), dt)
    return p


# ---------------------------------------------------------------------------
# core softmax-attention primitives
# ---------------------------------------------------------------------------

def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q (B,Sq,H,hd), k (B,Sk,KV,hd) -> scores (B,KV,G,Sq,Sk), H = KV*G."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    return jnp.einsum("bskgh,btkh->bkgst", qg, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(probs: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """probs (B,KV,G,Sq,Sk), v (B,Sk,KV,hd) -> (B,Sq,H,hd)."""
    b, kvh, g, sq, _ = probs.shape
    o = jnp.einsum("bkgst,btkh->bskgh", probs, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, kvh * g, v.shape[-1])


def direct_attention(q, k, v, q_pos, k_pos, window: int = 0) -> jnp.ndarray:
    """Masked-einsum attention; fine up to a few thousand tokens."""
    hd = q.shape[-1]
    scores = _gqa_scores(q, k) / jnp.sqrt(jnp.float32(hd))
    mask = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v).astype(q.dtype)


def chunked_attention(q, k, v, q_pos, k_pos, window: int = 0,
                      chunk: int = KV_CHUNK) -> jnp.ndarray:
    """Online-softmax attention scanned over KV chunks (flash-style).

    Never materializes (Sq, Sk); live memory is O(Sq * chunk) per head.
    """
    b, sq, h, hd = q.shape
    vd = v.shape[-1]                       # may differ from hd (MLA)
    sk = k.shape[1]
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=jnp.iinfo(jnp.int32).max)
    kc = k.reshape(b, n_chunks, chunk, k.shape[2], hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, v.shape[2], vd).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, chunk)

    kvh = k.shape[2]
    g = h // kvh
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def step(carry, blk):
        m, l, acc = carry                      # (B,KV,G,Sq), (..), (B,Sq,H,hd)f32
        kb, vb, pb = blk
        s = _gqa_scores(q, kb) * scale         # (B,KV,G,Sq,chunk)
        mask = pb[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= pb[None, :] > (q_pos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)             # rescale old accumulator
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o = _gqa_out(p, vb)                    # (B,Sq,H,hd) f32
        alpha_o = alpha.transpose(0, 3, 1, 2).reshape(b, sq, h)[..., None]
        acc_new = acc * alpha_o + o
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, h, vd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kc, vc, pc))
    denom = l.transpose(0, 3, 1, 2).reshape(b, sq, h)[..., None]
    return (acc / jnp.maximum(denom, 1e-30)).astype(q.dtype)


def attention_any(q, k, v, q_pos, k_pos, window: int = 0) -> jnp.ndarray:
    if k.shape[1] <= DIRECT_ATTN_MAX_SEQ:
        return direct_attention(q, k, v, q_pos, k_pos, window)
    return chunked_attention(q, k, v, q_pos, k_pos, window)


# ---------------------------------------------------------------------------
# GQA mixer: full sequence + decode
# ---------------------------------------------------------------------------

def _qkv(p: Params, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                 positions: jnp.ndarray, window: int = 0,
                 return_kv: bool = False):
    """Full-sequence causal attention. positions: (S,) int32."""
    q, k, v = _qkv(p, cfg, x, positions)
    o = attention_any(q, k, v, positions, positions, window)
    # row-parallel: cross-shard reduction in the activation dtype (bf16)
    # halves all-reduce bytes vs f32 (EXPERIMENTS.md §Perf rgemma iter 2)
    y = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"])
    if return_kv:
        return y, (k, v)
    return y


def attn_decode(p: Params, cfg: ModelConfig, x: jnp.ndarray, cache: Params,
                window: int = 0):
    """One-token decode. x (B,1,D); cache {'k','v': (B,Scache,KV,hd), 'pos'}.

    For window caches (ring buffers) ``Scache == window`` and slots hold
    absolute positions in ``cache['k_pos']``.
    """
    pos = cache["pos"]                              # scalar int32
    positions = pos[None]                            # (1,)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k1 = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v1 = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k1, v1 = q + p["bq"], k1 + p["bk"], v1 + p["bv"]
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k1 = apply_rope(k1, positions, cfg.rope_theta)

    s_cache = cache["k"].shape[1]
    slot = jnp.where(jnp.int32(window) > 0, pos % s_cache,
                     jnp.minimum(pos, s_cache - 1))
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k1.astype(cache["k"].dtype), slot, 1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v1.astype(cache["v"].dtype), slot, 1)
    k_pos = jax.lax.dynamic_update_slice_in_dim(
        cache["k_pos"], pos[None], slot, 0)

    o = direct_attention(q, k, v, positions, k_pos, window)
    # row-parallel: cross-shard reduction in the activation dtype (bf16)
    # halves all-reduce bytes vs f32 (EXPERIMENTS.md §Perf rgemma iter 2)
    y = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"])
    new_cache = {"k": k, "v": v, "k_pos": k_pos, "pos": pos + 1}
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA mixer (DeepSeek-V2): full sequence + absorbed decode
# ---------------------------------------------------------------------------

def _mla_q(p: Params, cfg: ModelConfig, x: jnp.ndarray, positions):
    hd, rh = cfg.hd, cfg.rope_head_dim
    if cfg.q_lora_rank > 0:
        cq = jnp.einsum("bsd,dr->bsr", x, p["w_dq"])
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                positions: jnp.ndarray, return_kv: bool = False):
    """Full-sequence MLA: materialize per-head K/V from the latent."""
    r, rh = cfg.kv_lora_rank, cfg.rope_head_dim
    hd = cfg.hd
    vh = cfg.v_head_dim or hd
    dkv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])        # (B,S,r+rh)
    ckv, krope = dkv[..., :r], dkv[..., r:]
    krope = apply_rope(krope[:, :, None, :], positions, cfg.rope_theta)  # (B,S,1,rh)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"])   # (B,S,H,hd)
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uv"])        # (B,S,H,vh)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)

    h = cfg.n_heads
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope, (*k_nope.shape[:2], h, rh))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = attention_any(q_full, k_full, v, positions, positions)
    # row-parallel: cross-shard reduction in the activation dtype (bf16)
    # halves all-reduce bytes vs f32 (EXPERIMENTS.md §Perf rgemma iter 2)
    y = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"])
    if return_kv:
        return y, (ckv.astype(x.dtype), krope[:, :, 0, :].astype(x.dtype))
    return y


def mla_decode(p: Params, cfg: ModelConfig, x: jnp.ndarray, cache: Params):
    """Absorbed-matmul MLA decode: attends in the rank-r latent space.

    cache: {'ckv': (B,S,r), 'krope': (B,S,rh), 'pos'}. Scores are
    q_eff·ckv + q_rope·krope where q_eff = q_nope @ W_uk (per head) — the
    per-head K is never materialized (this is MLA's decode-bandwidth win).
    """
    r, rh, hd = cfg.kv_lora_rank, cfg.rope_head_dim, cfg.hd
    vh = cfg.v_head_dim or hd
    pos = cache["pos"]
    positions = pos[None]
    dkv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    ckv1, krope1 = dkv[..., :r], dkv[..., r:]
    krope1 = apply_rope(krope1[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]

    s_cache = cache["ckv"].shape[1]
    slot = jnp.minimum(pos, s_cache - 1)
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["ckv"], ckv1.astype(cache["ckv"].dtype), slot, 1)
    krope = jax.lax.dynamic_update_slice_in_dim(
        cache["krope"], krope1.astype(cache["krope"].dtype), slot, 1)
    k_pos = jax.lax.dynamic_update_slice_in_dim(cache["k_pos"], pos[None], slot, 0)

    q_nope, q_rope = _mla_q(p, cfg, x, positions)          # (B,1,H,hd/rh)
    # absorb W_uk into the query:  (B,1,H,hd) x (r,H,hd) -> (B,1,H,r)
    q_eff = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"],
                       preferred_element_type=jnp.float32)
    scores = (jnp.einsum("bshr,btr->bhst", q_eff, ckv.astype(jnp.float32))
              + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                           krope.astype(jnp.float32)))
    scores = scores / jnp.sqrt(jnp.float32(hd + rh))
    mask = (k_pos[None, :] <= positions[:, None])          # (1,S)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)                # (B,H,1,S)
    # attend in latent space, then up-project with W_uv (absorbed output)
    o_lat = jnp.einsum("bhst,btr->bshr", probs, ckv.astype(jnp.float32))
    o = jnp.einsum("bshr,rhk->bshk", o_lat, p["w_uv"])     # (B,1,H,vh)
    # row-parallel: cross-shard reduction in the activation dtype (bf16)
    # halves all-reduce bytes vs f32 (EXPERIMENTS.md §Perf rgemma iter 2)
    y = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"])
    new_cache = {"ckv": ckv, "krope": krope, "k_pos": k_pos, "pos": pos + 1}
    return y, new_cache
