"""The layer-group-scanned decoder stack covering every assigned architecture.

The stack is a ``jax.lax.scan`` over *groups* of the repeating
``cfg.layer_pattern`` with stacked params (HLO size stays O(|pattern|), not
O(n_layers) — required for 95-layer deepseek-67b at 32k tokens), plus a short
unscanned tail for the ``n_layers % |pattern|`` remainder layers.

Three entry points:
  forward(...)      full-sequence logits (training)
  prefill(...)      full-sequence logits + a primed decode cache
  decode_step(...)  one token against the cache
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.cache import (full_kv_to_cache, init_cache, mla_kv_to_cache)
from repro.models.common import (ModelConfig, Params, dense_init, embed_init,
                                 init_rmsnorm, rmsnorm)

MIXER_KINDS = ("global", "local", "mla", "ssd", "rec", "moe")

# each layer's ops carry the scope ``<cfg.name>.<kind's scope>``
_SCOPE = {"global": "attn", "local": "attn", "mla": "attn", "ssd": "mamba",
          "rec": "rglru", "moe": "moe"}


def _has_ffn(cfg: ModelConfig, kind: str) -> bool:
    """Whether a layer ends in an FFN. A pattern with an expert layer of
    its own ("moe", Nemotron-H) is made of single-op blocks, none of
    which carries one."""
    return cfg.d_ff > 0 and "moe" not in cfg.layer_pattern


# ---------------------------------------------------------------------------
# ZeRO-3 layer-weight gather hook
# ---------------------------------------------------------------------------
# Under the FSDP sharding policy, expert weights are STORED data-sharded; the
# hook applies a with_sharding_constraint to each scan group's param slice so
# GSPMD all-gathers the WEIGHTS at use (per layer group, inside the scan —
# live footprint is one group's worth) instead of resharding activations,
# which measured a 3.2x flop regression (EXPERIMENTS.md §Perf dsv2 iter 2).
_LAYER_PARAM_HOOK = None


def set_layer_param_hook(fn) -> None:
    """fn(group_params_dict) -> constrained dict, or None to disable."""
    global _LAYER_PARAM_HOOK
    _LAYER_PARAM_HOOK = fn


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, kind: str) -> Params:
    k1, k2 = jax.random.split(key)
    p: Dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model, cfg.param_dtype)}
    if kind in ("global", "local"):
        p["mixer"] = attn.init_attention(k1, cfg)
    elif kind == "mla":
        p["mixer"] = attn.init_mla(k1, cfg)
    elif kind == "ssd":
        p["mixer"] = ssm_mod.init_ssd(k1, cfg)
    elif kind == "rec":
        p["mixer"] = rglru_mod.init_rglru(k1, cfg)
    elif kind == "moe":
        p["mixer"] = ffn_mod.init_held_moe(k1, cfg)
    else:
        raise ValueError(f"unknown mixer kind {kind!r}")
    if _has_ffn(cfg, kind):
        p["norm2"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
        p["ffn"] = (ffn_mod.init_moe(k2, cfg) if cfg.is_moe
                    else ffn_mod.init_dense_ffn(k2, cfg))
    return p


def _apply_mixer_full(p, cfg, kind, h, positions, want_cache: bool):
    if kind in ("global", "local"):
        window = cfg.sliding_window if kind == "local" else 0
        if want_cache:
            y, (k, v) = attn.attn_forward(p, cfg, h, positions, window,
                                          return_kv=True)
            return y, ("kv", k, v, window)
        return attn.attn_forward(p, cfg, h, positions, window), None
    if kind == "mla":
        if want_cache:
            y, (ckv, krope) = attn.mla_forward(p, cfg, h, positions,
                                               return_kv=True)
            return y, ("mla", ckv, krope)
        return attn.mla_forward(p, cfg, h, positions), None
    if kind == "ssd":
        if want_cache:
            y, c = ssm_mod.ssd_forward(p, cfg, h, return_state=True)
            return y, ("state", c)
        return ssm_mod.ssd_forward(p, cfg, h), None
    if kind == "rec":
        if want_cache:
            y, c = rglru_mod.rglru_forward(p, cfg, h, return_state=True)
            return y, ("state", c)
        return rglru_mod.rglru_forward(p, cfg, h), None
    raise ValueError(kind)


def apply_layer(p: Params, cfg: ModelConfig, kind: str, x: jnp.ndarray,
                positions: jnp.ndarray, moe_path: str = "gshard",
                cache_seq: int = 0):
    """Full-sequence layer. Returns (x, aux_loss, cache_or_None, load):
    ``load`` is an expert layer's token-choice count per held expert,
    None for other kinds."""
    with jax.named_scope(f"{cfg.name}.{_SCOPE[kind]}"):
        h = rmsnorm(p["norm1"], x, cfg.norm_eps)
        want_cache = cache_seq > 0
        load = None
        if kind == "moe":
            y, load = ffn_mod.held_moe_forward(p["mixer"], cfg, h)
            raw = ("none", {})
        else:
            y, raw = _apply_mixer_full(p["mixer"], cfg, kind, h, positions,
                                       want_cache)
        x = x + y
        aux = jnp.zeros((), jnp.float32)
        if _has_ffn(cfg, kind):
            h = rmsnorm(p["norm2"], x, cfg.norm_eps)
            if cfg.is_moe:
                y, aux = ffn_mod.moe_forward(p["ffn"], cfg, h, path=moe_path)
            else:
                y = ffn_mod.dense_ffn(p["ffn"], h)
            x = x + y
    cache = None
    if want_cache:
        if raw[0] == "kv":
            _, k, v, window = raw
            cache = full_kv_to_cache(k, v, cache_seq, window)
        elif raw[0] == "mla":
            cache = mla_kv_to_cache(raw[1], raw[2], cache_seq)
        else:
            cache = raw[1]
    return x, aux, cache, load


def apply_layer_decode(p: Params, cfg: ModelConfig, kind: str,
                       x: jnp.ndarray, cache: Params):
    """One-token layer step. Returns (x, new_cache)."""
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if kind in ("global", "local"):
        window = cfg.sliding_window if kind == "local" else 0
        y, nc = attn.attn_decode(p["mixer"], cfg, h, cache, window)
    elif kind == "mla":
        y, nc = attn.mla_decode(p["mixer"], cfg, h, cache)
    elif kind == "ssd":
        y, nc = ssm_mod.ssd_decode(p["mixer"], cfg, h, cache)
    elif kind == "rec":
        y, nc = rglru_mod.rglru_decode(p["mixer"], cfg, h, cache)
    else:
        raise ValueError(kind)
    x = x + y
    if _has_ffn(cfg, kind):
        h = rmsnorm(p["norm2"], x, cfg.norm_eps)
        if cfg.is_moe:
            y, _ = ffn_mod.moe_decode(p["ffn"], cfg, h)
        else:
            y = ffn_mod.dense_ffn(p["ffn"], h)
        x = x + y
    return x, nc


# ---------------------------------------------------------------------------
# whole-stack init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, len(cfg.layer_pattern) + 4)
    p: Dict[str, Any] = {
        "embed": embed_init(keys[0], (cfg.vocab_size, cfg.d_model),
                            cfg.param_dtype),
        "final_norm": init_rmsnorm(cfg.d_model, cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab_size),
                                  cfg.param_dtype)
    if cfg.frontend is not None:
        from repro.models.frontends import frontend_dim
        p["frontend_proj"] = dense_init(
            keys[2], (frontend_dim(cfg.frontend), cfg.d_model),
            cfg.param_dtype)
    p.update(init_stack(key, cfg, keys[3:]))
    return p


def init_stack(key, cfg: ModelConfig, pos_keys=None) -> Params:
    """The layer stack's params: ``groups`` (pos_i -> stacked (G, ...))
    and the unscanned remainder ``rem``."""
    if pos_keys is None:
        pos_keys = jax.random.split(key, len(cfg.layer_pattern))
    groups: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.layer_pattern):
        ks = jax.random.split(pos_keys[i], max(cfg.n_groups, 1))
        groups[f"pos{i}"] = jax.vmap(
            lambda k, kind=kind: init_layer(k, cfg, kind))(ks[:cfg.n_groups])
    rem_key = jax.random.split(key, cfg.n_remainder + 1)
    return {"groups": groups,
            "rem": [init_layer(rem_key[i], cfg, cfg.layer_pattern[i])
                    for i in range(cfg.n_remainder)]}


def abstract_params(cfg: ModelConfig, seed: int = 0) -> Params:
    """ShapeDtypeStruct pytree — zero allocation; used by the dry-run."""
    return jax.eval_shape(functools.partial(init_params, cfg=cfg),
                          jax.random.key(seed))


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed_inputs(params: Params, cfg: ModelConfig,
                 tokens: Optional[jnp.ndarray],
                 embeds: Optional[jnp.ndarray]) -> jnp.ndarray:
    parts = []
    if embeds is not None:
        parts.append(jnp.einsum("bse,ed->bsd", embeds.astype(cfg.param_dtype),
                                params["frontend_proj"]))
    if tokens is not None:
        parts.append(jnp.take(params["embed"], tokens, axis=0))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)


def lm_logits(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return jnp.einsum("bsd,dv->bsv", x, head,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward / prefill / decode
# ---------------------------------------------------------------------------

def _stack_body(cfg: ModelConfig, positions, moe_path: str, cache_seq: int,
                remat_layers: bool = False):
    pattern = cfg.layer_pattern

    def layer(kind):
        def f(p, x):
            return apply_layer(p, cfg, kind, x, positions, moe_path,
                               cache_seq)
        return jax.checkpoint(f) if remat_layers else f

    def body(carry, gp):
        x, aux = carry
        if _LAYER_PARAM_HOOK is not None:
            gp = _LAYER_PARAM_HOOK(gp)
        caches, loads = {}, {}
        for i, kind in enumerate(pattern):
            x, a, c, load = layer(kind)(gp[f"pos{i}"], x)
            aux = aux + a
            if cache_seq > 0:
                caches[f"pos{i}"] = c
            if load is not None:
                loads[f"pos{i}"] = load
        return (x, aux), {"cache": caches if cache_seq > 0 else None,
                          "load": loads}

    return body


def run_stack(params: Params, cfg: ModelConfig, x: jnp.ndarray,
              positions: Optional[jnp.ndarray] = None,
              moe_path: str = "gshard", remat: bool = False,
              remat_layers: bool = False):
    """The layer stack on embedded inputs x (B,S,D). Returns (x, moe aux
    loss, loads): ``loads`` (n_expert_layers, n_held) int32 is each
    "moe" layer's token-choice count per held expert, in layer order
    (None where the pattern has none).

    ``remat=True`` checkpoints each scan group (activation recompute in
    the backward pass) — required for the big archs' train_step to fit
    HBM. ``remat_layers=True`` checkpoints each layer instead, so the
    backward holds one layer's internals at a time: what a stack of one
    long group (Nemotron-H's period) needs."""
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    aux = jnp.zeros((), jnp.float32)
    loads = []
    if cfg.n_groups > 0:
        body = _stack_body(cfg, positions, moe_path, 0, remat_layers)
        if remat:
            body = jax.checkpoint(body)
        (x, aux), ys = jax.lax.scan(body, (x, aux), params["groups"])
        group_loads = [ys["load"][f"pos{i}"]
                       for i, kind in enumerate(cfg.layer_pattern)
                       if kind == "moe"]
        if group_loads:                 # (G, n, held) in layer order
            loads.append(jnp.stack(group_loads, axis=1).reshape(
                -1, group_loads[0].shape[-1]))
    for i, p in enumerate(params["rem"]):
        layer = functools.partial(apply_layer, cfg=cfg,
                                  kind=cfg.layer_pattern[i],
                                  positions=positions, moe_path=moe_path,
                                  cache_seq=0)
        if remat or remat_layers:
            layer = jax.checkpoint(lambda p_, x_, f=layer: f(p_, x=x_))
            x, a, _, load = layer(p, x)
        else:
            x, a, _, load = layer(p, x=x)
        aux = aux + a
        if load is not None:
            loads.append(load[None])
    return x, aux, (jnp.concatenate(loads) if loads else None)


def forward(params: Params, cfg: ModelConfig,
            tokens: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            moe_path: str = "gshard",
            remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits (B,S,V) fp32, moe_aux_loss scalar); ``remat`` as
    in ``run_stack``."""
    x = embed_inputs(params, cfg, tokens, embeds)
    x, aux, _ = run_stack(params, cfg, x, positions, moe_path, remat)
    return lm_logits(params, cfg, x), aux


def prefill(params: Params, cfg: ModelConfig,
            tokens: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None,
            cache_seq: int = 0,
            moe_path: str = "gshard"):
    """Full-sequence forward that also primes a decode cache of capacity
    ``cache_seq`` (>= prompt length). Returns (logits, cache)."""
    x = embed_inputs(params, cfg, tokens, embeds)
    s = x.shape[1]
    cache_seq = max(cache_seq, s)
    positions = jnp.arange(s, dtype=jnp.int32)
    aux0 = jnp.zeros((), jnp.float32)
    group_caches = {}
    if cfg.n_groups > 0:
        body = _stack_body(cfg, positions, moe_path, cache_seq)
        (x, _), ys = jax.lax.scan(body, (x, aux0), params["groups"])
        group_caches = ys["cache"]
    rem_caches: List[Params] = []
    for i, p in enumerate(params["rem"]):
        x, _, c, _ = apply_layer(p, cfg, cfg.layer_pattern[i], x, positions,
                                 moe_path, cache_seq)
        rem_caches.append(c)
    cache = {"groups": group_caches, "rem": rem_caches}
    return lm_logits(params, cfg, x), cache


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                cache: Params):
    """token (B,1) int32 -> (logits (B,1,V) fp32, new cache)."""
    x = embed_inputs(params, cfg, token, None)
    pattern = cfg.layer_pattern

    def body(x, inp):
        gp, gc = inp
        new = {}
        for i, kind in enumerate(pattern):
            x, nc = apply_layer_decode(gp[f"pos{i}"], cfg, kind, x,
                                       gc[f"pos{i}"])
            new[f"pos{i}"] = nc
        return x, new

    new_group_caches = cache["groups"]
    if cfg.n_groups > 0:
        x, new_group_caches = jax.lax.scan(
            body, x, (params["groups"], cache["groups"]))
    new_rem = []
    for i, p in enumerate(params["rem"]):
        x, nc = apply_layer_decode(p, cfg, cfg.layer_pattern[i], x,
                                   cache["rem"][i])
        new_rem.append(nc)
    logits = lm_logits(params, cfg, x)
    return logits, {"groups": new_group_caches, "rem": new_rem}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def token_ce_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean next-token cross-entropy. logits (B,S,V) fp32, labels (B,S).

    Sharding-aware formulation (EXPERIMENTS.md §Perf/qwen2 iteration 1):
    ``take_along_axis`` on a vocab-sharded logits array forces GSPMD to
    all-gather the full fp32 (B,S,V) tensor (~40 GB/device for qwen2 at
    train_4k). logsumexp + an iota-one-hot contraction keep every reduction
    over the sharded V axis (partial sums + a tiny (B,S) all-reduce) and
    never materialize log_softmax."""
    lse = jax.nn.logsumexp(logits, axis=-1)                  # (B,S)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
              == labels[..., None])
    picked = jnp.sum(logits * onehot.astype(logits.dtype), axis=-1)
    ll = picked - lse
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def lm_loss(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            moe_path: str = "gshard", aux_weight: float = 0.01,
            remat: bool = False):
    logits, aux = forward(params, cfg, tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"), moe_path=moe_path,
                          remat=remat)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:      # vlm: loss on text tail only
        logits = logits[:, -labels.shape[1]:]
    loss = token_ce_loss(logits, labels, batch.get("mask"))
    return loss + aux_weight * aux, (loss, aux)
