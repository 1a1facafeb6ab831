"""Mamba-2 SSD (state-space duality) mixer [arXiv:2405.21060].

TPU adaptation: the SSD chunked form is used for train/prefill — quadratic
attention-like compute *within* VMEM-sized chunks (MXU-friendly matmuls) and a
tiny recurrent state handoff *across* chunks (``lax.scan``). Decode is the
constant-memory recurrence. Scalar-per-head A; ``cfg.ssm_groups`` B/C
groups, each shared by ``ssm_heads / ssm_groups`` consecutive heads, and
the gated RMSNorm taken over the same groups of ``d_inner / ssm_groups``
channels (mamba_ssm's ``RMSNormGated(group_size=d_inner/ngroups,
norm_before_gate=False)``).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, Params, dense_init


def _dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    di = cfg.d_inner
    h = cfg.ssm_heads
    p = di // h
    n = cfg.ssm_state
    return di, h, p, n


def init_ssd(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    di, h, p, n = _dims(cfg)
    gn = cfg.ssm_groups * n
    dt = cfg.param_dtype
    conv_ch = di + 2 * gn                      # conv over [x, B, C]
    ks = jax.random.split(key, 4)
    return {
        # in_proj -> [z (di), x (di), B (G*n), C (G*n), dt (h)]
        "w_in": dense_init(ks[0], (d, 2 * di + 2 * gn + h), dt),
        "conv_w": dense_init(ks[1], (cfg.conv_width, conv_ch), dt,
                             fan_in=cfg.conv_width),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "a_log": jnp.zeros((h,), jnp.float32),              # A = -exp(a_log)
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm_scale": jnp.ones((di,), dt),                  # gated RMSNorm
        "w_out": dense_init(ks[3], (di, d), dt, fan_in=di),
    }


def _split_in(p: Params, cfg: ModelConfig, x: jnp.ndarray):
    di, h, _, n = _dims(cfg)
    gn = cfg.ssm_groups * n
    proj = jnp.einsum("bsd,de->bse", x, p["w_in"])
    z = proj[..., :di]
    xbc = proj[..., di:2 * di + 2 * gn]
    dt_raw = proj[..., 2 * di + 2 * gn:]
    return z, xbc, dt_raw


def _split_xbc(cfg: ModelConfig, xbc: jnp.ndarray):
    """[x (di), B (G*n), C (G*n)] -> x, B (...,G,n), C (...,G,n)."""
    di, _, _, n = _dims(cfg)
    g = cfg.ssm_groups
    lead = xbc.shape[:-1]
    return (xbc[..., :di],
            xbc[..., di:di + g * n].reshape(*lead, g, n),
            xbc[..., di + g * n:].reshape(*lead, g, n))


def _gated_norm(p: Params, y: jnp.ndarray, z: jnp.ndarray, eps: float,
                groups: int = 1) -> jnp.ndarray:
    """RMSNorm of y * silu(z) over ``groups`` equal channel groups."""
    yf = (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.float32)
    yg = yf.reshape(*yf.shape[:-1], groups, yf.shape[-1] // groups)
    var = jnp.mean(yg * yg, axis=-1, keepdims=True)
    return ((yg * jax.lax.rsqrt(var + eps)).reshape(yf.shape)
            * p["norm_scale"].astype(jnp.float32))


def _causal_conv(p: Params, u: jnp.ndarray, prior: jnp.ndarray = None):
    """Depthwise causal conv, width W. u (B,S,C). prior: (B,W-1,C) history."""
    w = p["conv_w"]                                         # (W, C)
    width = w.shape[0]
    if prior is None:
        prior = jnp.zeros((u.shape[0], width - 1, u.shape[-1]), u.dtype)
    up = jnp.concatenate([prior, u], axis=1)
    out = sum(up[:, i:i + u.shape[1], :] * w[i] for i in range(width))
    return jax.nn.silu((out + p["conv_b"]).astype(jnp.float32)).astype(u.dtype)


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """x (..., q) -> (..., q, q) with S[i,j] = sum_{j<k<=i} x[k], -inf above diag."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_scan(xh: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b_: jnp.ndarray, c_: jnp.ndarray, chunk: int,
             init_state: jnp.ndarray = None):
    """Chunked SSD.

    xh (B,S,H,P) head inputs; dt (B,S,H) positive step sizes; a (H,) negative;
    b_/c_ (B,S,G,N) SSM in/out projections of G groups, head h reading
    group h // (H/G).
    Returns (y (B,S,H,P) fp32, final_state (B,H,P,N) fp32).
    """
    bsz, s, h, p = xh.shape
    g, n = b_.shape[-2:]
    k = h // g                                              # heads per group
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_ = jnp.pad(b_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_ = jnp.pad(c_, ((0, 0), (0, pad), (0, 0), (0, 0)))

    q = chunk
    xc = xh.reshape(bsz, nc, q, g, k, p).astype(jnp.float32)
    dtc = dt.reshape(bsz, nc, q, g, k).astype(jnp.float32)
    bc = b_.reshape(bsz, nc, q, g, n).astype(jnp.float32)
    cc = c_.reshape(bsz, nc, q, g, n).astype(jnp.float32)

    da = dtc * a.reshape(g, k)                              # (B,C,Q,G,K) <= 0
    da_cs = jnp.cumsum(da, axis=2)                          # within-chunk
    x_dt = xc * dtc[..., None]                              # dt-discretized input

    # 1) within-chunk (quadratic, MXU): L[b,c,g,k,i,j] decay, i >= j
    l_mat = jnp.exp(_segsum(da.transpose(0, 1, 3, 4, 2)))   # (B,C,G,K,Q,Q)
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc)           # (B,C,G,Q,Q)
    y_diag = jnp.einsum("bcgij,bcgkij,bcjgkp->bcigkp", cb, l_mat, x_dt)

    # 2) per-chunk end states
    decay_to_end = jnp.exp(da_cs[:, :, -1:] - da_cs)        # (B,C,Q,G,K)
    states = jnp.einsum("bcjgn,bcjgk,bcjgkp->bcgkpn", bc, decay_to_end, x_dt)

    # 3) cross-chunk recurrence (tiny scan over chunk index)
    chunk_decay = jnp.exp(jnp.sum(da, axis=2))              # (B,C,G,K)

    def step(carry, inp):
        st, dec = inp                                       # (B,G,K,P,N),(B,G,K)
        prev = carry
        new = prev * dec[..., None, None] + st
        return new, prev

    init = (jnp.zeros((bsz, g, k, p, n), jnp.float32) if init_state is None
            else init_state.astype(jnp.float32).reshape(bsz, g, k, p, n))
    final_state, prev_states = jax.lax.scan(
        step, init, (jnp.moveaxis(states, 1, 0),
                     jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)           # (B,C,G,K,P,N)

    # 4) contribution of previous chunks' state
    in_decay = jnp.exp(da_cs)                               # (B,C,Q,G,K)
    y_off = jnp.einsum("bcign,bcgkpn,bcigk->bcigkp", cc, prev_states,
                       in_decay)

    y = (y_diag + y_off).reshape(bsz, nc * q, h, p)[:, :s]
    return y, final_state.reshape(bsz, h, p, n)


def ssd_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                return_state: bool = False):
    """Full-sequence Mamba-2 mixer. x (B,S,D) -> (B,S,D)."""
    di, h, ph, n = _dims(cfg)
    z, xbc, dt_raw = _split_in(p, cfg, x)
    conv_out = _causal_conv(p, xbc)
    xin, b_, c_ = _split_xbc(cfg, conv_out)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    xh = xin.reshape(*xin.shape[:2], h, ph)
    y, state = ssd_scan(xh, dt, a, b_, c_, cfg.ssm_chunk)
    y = y + p["d_skip"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(*x.shape[:2], di)
    y = _gated_norm(p, y, z, cfg.norm_eps, cfg.ssm_groups)
    out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), p["w_out"])
    if return_state:
        conv_tail = xbc[:, -(cfg.conv_width - 1):, :]
        return out, {"state": state, "conv": conv_tail}
    return out


def ssd_decode(p: Params, cfg: ModelConfig, x: jnp.ndarray, cache: Params):
    """One-token recurrent step. cache: {'state': (B,H,P,N), 'conv': (B,W-1,C)}."""
    di, h, ph, n = _dims(cfg)
    g = cfg.ssm_groups
    z, xbc, dt_raw = _split_in(p, cfg, x)                   # all (B,1,·)
    conv_out = _causal_conv(p, xbc, prior=cache["conv"])
    new_conv = jnp.concatenate([cache["conv"], xbc], axis=1)[:, 1:, :]
    xin, b_, c_ = _split_xbc(cfg, conv_out)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])[:, 0]  # (B,H)
    a = -jnp.exp(p["a_log"])
    xh = xin[:, 0].reshape(-1, h, ph).astype(jnp.float32)   # (B,H,P)
    # each head reads its group's B and C
    bv = jnp.repeat(b_[:, 0].astype(jnp.float32), h // g, axis=1)  # (B,H,N)
    cv = jnp.repeat(c_[:, 0].astype(jnp.float32), h // g, axis=1)
    decay = jnp.exp(dt * a)                                 # (B,H)
    dx = xh * dt[..., None]                                 # (B,H,P)
    state = (cache["state"] * decay[..., None, None]
             + jnp.einsum("bhp,bhn->bhpn", dx, bv))
    y = jnp.einsum("bhpn,bhn->bhp", state, cv) + p["d_skip"][:, None] * xh
    y = y.reshape(x.shape[0], 1, di)
    y = _gated_norm(p, y, z, cfg.norm_eps, g)
    out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), p["w_out"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return out, {"state": state, "conv": new_conv}


def ssd_init_cache(cfg: ModelConfig, batch: int, dtype) -> Params:
    di, h, ph, n = _dims(cfg)
    conv_ch = di + 2 * cfg.ssm_groups * n
    return {
        "state": jnp.zeros((batch, h, ph, n), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_ch), dtype),
    }
